"""The serve cells' weight files: drawn from the seed alone, at the
configuration's shapes and dtype, under the names of the program's
parameter tree, and read back alike by the program and the reference."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from chipbench import weights as W
from chipbench.tests import tiny_serve as TS

CFG = json.loads((TS.DATA / f"{TS.CONFIG}.json").read_text())


def draw(tmp_path, name, c=CFG, seed=5, threads=2):
    return W.draw(c, seed, tmp_path / name, threads=threads)


def test_the_same_seed_draws_the_same_files_on_any_thread_count(tmp_path,
                                                                 monkeypatch):
    monkeypatch.setattr(W, "CHUNK", 1000)       # many chunks a file
    a, b = draw(tmp_path, "a", threads=1), draw(tmp_path, "b", threads=3)
    c = draw(tmp_path, "c", seed=6)
    for name in W.files(CFG):
        x, y, z = (np.load(d / f"{name}.npy") for d in (a, b, c))
        np.testing.assert_array_equal(x, y)
        assert not np.array_equal(x, z)
    assert not list(tmp_path.glob("*.tmp"))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_program_loads_the_files_the_reference_reads(tmp_path, dtype):
    from repro.ckpt import read_leaves
    from repro.models.model import build

    c = dict(CFG, dtype=dtype)
    d = draw(tmp_path, "w", c)
    cfg = dataclasses.replace(TS.tiny_config(), param_dtype=dtype)
    like = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    params = read_leaves(str(d), like)
    ref = W.by_role(d, c)
    assert len(ref["layers"]) == CFG["n_layers"]
    pairs = [(params["embed"]["table"], ref["embed"]),
             (params["embed"]["head"], ref["head"]),
             (params["final_norm"]["scale"], ref["final_norm"])]
    for i, layer in enumerate(ref["layers"]):
        for role, name in W.LAYER_FILES.items():
            _, a, b = name.split(".")
            pairs.append((params["layers"][a][b][i], layer[role]))
    for got, want in pairs:
        assert got.dtype == want.dtype == np.dtype(W.dtype_of(c))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_values_have_the_initialiser_spread(tmp_path):
    c = dict(CFG, d_model=256, d_ff=512)
    d = draw(tmp_path, "w", c)
    wq = np.asarray(W.load(d, "layers.attn.wq", c), np.float64)
    assert wq.std() == pytest.approx(256 ** -0.5, rel=0.02)
    norm = np.asarray(W.load(d, "layers.ln1.scale", c), np.float64)
    assert 0.75 <= norm.min() < 0.8 and 1.2 < norm.max() <= 1.25
