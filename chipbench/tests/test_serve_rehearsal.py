"""Whole runs of the serve-campaign runner on the CPU, at a tiny
DeepSeek-Coder-shaped configuration: a sound run is correct, and each
fault the cell can have, planted where the program makes its answer, makes
it not correct or stops it in set-up."""
import time

import pytest

from chipbench import harness as H
from chipbench.tests import tiny_serve as TS

SEED = 3000001425


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return TS.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(autouse=True)
def tiny_arch(monkeypatch):
    from repro.serve.load import release_serve_engines

    TS.register_tiny_config(monkeypatch)
    yield
    release_serve_engines()


def run(root, trace=0, hook=None):
    return H.run_cell(H.parse(["--workload", TS.CELL, "--seed", str(SEED),
                               "--seconds", "0.5", "--trace", str(trace)]),
                      t_start=time.perf_counter(), root=root,
                      require_chip=False, compile_cache=False,
                      cell_hook=hook)


def test_serve_campaign_rehearsal_traced(root):
    got = {}
    out = run(root, trace=1, hook=lambda c: got.setdefault("cell", c))
    assert out["correct"] is True
    assert out["attempted"] == 1 and out["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    # the device-trace readers need a chip's programs and peaks
    assert set(out["metrics"]) == {"campaign.serve_build_s"}
    assert out["metrics"]["campaign.serve_build_s"]["value"] > 0
    cell = got["cell"]
    assert cell.warm_up["tick_weight_bytes"] == 525_568
    # each slot's prompt, two warm ticks' tokens and the probed tick's own
    assert cell.warm_up["live_positions"] == 32 + 21 + 13 + 7 + 4 * 3
    assert cell.tick_counts["tick_bytes"] > 525_568
    # the run's weight files are gone once it has been checked
    assert not list(cell.work.glob("weights-*"))
    (camp,) = cell.campaigns
    assert camp["points"] > 0 and len(camp["report"]) == 1


def alter_a_logit(mp):
    import repro.serve.engine as engine

    make = engine._make_paged_fns

    def altered(cfg, temperature):
        prefill, tick = make(cfg, temperature)

        def tick2(*args):
            *rest, logits = tick(*args)
            return (*rest, logits.at[1, 3].add(1.0))

        return prefill, tick2

    mp.setattr(engine, "_make_paged_fns", altered)


def skip_the_payload_check(mp):
    from repro.core.controller import Controller

    mp.setattr(Controller, "verify_mode_payload",
               lambda self, target, mode, ks: None)


def round_the_weights_through_float8(mp):
    """The program's weight load rounds every weight through float8_e4m3fn:
    the engine holds float32 arrays of float8 values."""
    import repro.serve.load as load
    from chipbench.calibrate_decoder import rounded_load

    mp.setattr(load, "load_weights", rounded_load(load.load_weights))


FAULTS = {
    "an altered logit": (alter_a_logit, "tick_logits_max_rel_err"),
    "float8 weights in the program's build": (
        round_the_weights_through_float8, "tick_logits_max_rel_err"),
    "a skipped payload check": (skip_the_payload_check, "pairs_unverified"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_serve_campaign_is_not_correct(root, monkeypatch, fault):
    plant, caught_by = FAULTS[fault]
    plant(monkeypatch)
    out = run(root)
    assert out["correct"] is False
    check = out["checks"][caught_by]
    assert check["value"] > check["limit"]


def test_the_smoke_engine_fails_the_size_check_in_set_up(root,
                                                         monkeypatch):
    """A program that builds its smoke engine for the target, as one
    without the published serve kind does, stops in set-up."""
    import repro.serve.load as load
    from chipbench.runners.serve_campaign import SizeCheckError

    smoke = load.build_serve_regions
    monkeypatch.setattr(load, "build_serve_target", lambda params, modes:
                        smoke("deepseek-coder-33b", list(modes)))
    t0 = time.perf_counter()
    with pytest.raises(SizeCheckError, match="size check"):
        run(root)
    assert time.perf_counter() - t0 < 60


def test_the_float8_control_fails_the_logits_limit(root):
    from chipbench.calibrate_decoder import NAME, tick_readings

    got = {}
    run(root, hook=lambda c: got.setdefault("cell", c))
    cell = got["cell"]
    limit = cell.mix["correct"][NAME]
    for _seed, program, control in tick_readings(cell, [3, 4]):
        assert program[NAME] < limit < control[NAME]
