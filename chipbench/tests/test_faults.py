"""With the timed path broken underneath, a run's ``correct`` comes out
false: once for each fault the campaign cell can have, each planted where
the program produces the answer."""
import dataclasses
import time

import pytest

from chipbench import harness as H
from chipbench.tests.tiny import make_root

ARGS = ["--seed", "31337", "--seconds", "0.5", "--trace", "0"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def run(root):
    return H.run_cell(H.parse(["--workload", "tiny.campaign", *ARGS]),
                      t_start=time.perf_counter(), root=root,
                      require_chip=False, compile_cache=False)


def alter_spmxv_answer(mp):
    import repro.kernels.region as region

    rt = region.spmv_ell_pallas_rt

    def altered(k, vals, cols, x, **kw):
        y, *rest = rt(k, vals, cols, x, **kw)
        return (y.at[3].add(1.0), *rest)

    mp.setattr(region, "spmv_ell_pallas_rt", altered)


def fail_the_payload_check(mp):
    from repro.core.controller import Controller, PayloadError

    def fails(self, target, mode, ks):
        raise PayloadError(f"{target.name}/{mode}: noise did not survive")

    mp.setattr(Controller, "verify_mode_payload", fails)


def skip_the_payload_check(mp):
    from repro.core.controller import Controller

    mp.setattr(Controller, "verify_mode_payload",
               lambda self, target, mode, ks: None)


def sweep_fewer_points(mp):
    from repro.core.controller import Controller

    ks_for = Controller._ks_for
    mp.setattr(Controller, "_ks_for",
               lambda self, sensitivity: ks_for(self, sensitivity)[:-2])


def shift_the_fit(mp):
    import repro.core.campaign as campaign

    absorption = campaign.absorption

    def shifted(curve, **kw):
        fit = absorption(curve, **kw)
        return dataclasses.replace(fit, k1=fit.k1 + 1.0)

    mp.setattr(campaign, "absorption", shifted)


def alter_the_verdict(mp):
    import repro.core.campaign as campaign

    classify = campaign.classify

    def altered(absorptions, **kw):
        rep = classify(absorptions, **kw)
        other = "compute" if rep.label != "compute" else "latency"
        return dataclasses.replace(rep, label=other)

    mp.setattr(campaign, "classify", altered)


FAULTS = {
    "an altered SPMXV answer": (alter_spmxv_answer, "spmxv_max_rel_err"),
    "a campaign whose payload check fails":
        (fail_the_payload_check, "campaigns_failed"),
    "a skipped payload check": (skip_the_payload_check, "pairs_unverified"),
    "a sweep cut short": (sweep_fewer_points, "pairs_off_grid"),
    "a shifted fit": (shift_the_fit, "fits_off"),
    "an altered verdict": (alter_the_verdict, "verdicts_off"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_campaign_is_not_correct(root, monkeypatch, fault):
    plant, caught_by = FAULTS[fault]
    plant(monkeypatch)
    out = run(root)
    assert out["correct"] is False
    check = out["checks"][caught_by]
    assert check["value"] > check["limit"]
