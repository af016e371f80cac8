"""The decode tick's byte and operation counts against hand-computed
values for the DeepSeek-Coder-33B cell (8 of 62 layers, 4 slots)."""
import json

from chipbench import counts_decoder as C
from chipbench.harness import BENCH_DIR

CFG = json.loads((BENCH_DIR / "configs" / "deepseek-coder-33b.8of62.json")
                 .read_text())
# prompts 2048 + 1531 + 1109 + 742, each with 2 warm ticks' tokens and the
# probed tick's own: 5,430 + 4 x 3
LIVE = 5_442


def test_weights_of_one_layer_and_of_a_tick():
    # q, o 7168 x 7168 each; k, v 7168 x 1024 each; SwiGLU 3 x 7168 x 19200;
    # two norms of 7168
    assert C.layer_params(CFG) == 530_331_648
    # 8 layers, the final norm, the head 7168 x 32256 (231,211,008)
    assert C.tick_weight_params(CFG) == 4_473_871_360
    assert C.tick_weight_params(CFG) * C.param_bytes(CFG) == 8_947_742_720
    # with the embedding table: the configuration's parameters
    assert C.tick_weight_params(CFG) + 7168 * 32256 == 4_705_082_368


def test_bytes_of_the_probed_tick():
    # 2 (k, v) x 8 layers x 8 heads x 128 x 2 bytes
    assert C.kv_bytes_per_position(CFG) == 32_768
    # weights + 4 embedding rows (57,344) + 5,442 positions (178,323,456)
    assert C.tick_bytes(CFG, 4, LIVE) == 9_126_123_520


def test_operations_of_the_probed_tick():
    assert C.tick_matmul_flops(CFG, 4) == 35_789_996_032
    # 2 x (scores + values) x 56 heads x 128 x 8 layers
    assert C.attention_flops_per_position(CFG) == 229_376
    assert C.tick_flops(CFG, 4, LIVE) == 35_789_996_032 + LIVE * 229_376 \
        == 37_038_260_224
