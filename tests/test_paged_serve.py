"""Paged KV-cache serving: dense/paged numerical equivalence, page-pool
lifecycle (refill, retire, free/reuse, stall/resume), engine bookkeeping
fixes (uid monotonicity, late submissions, declared-axis scatter), and the
fleet's "serve" target kind end to end (classify + replay)."""
import dataclasses
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as tf
from repro.models.model import build
from repro.serve import ServeEngine

ARCH = "deepseek_coder_33b"


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config(ARCH)
    api = build(cfg)
    return api, api.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def smoke_f32():
    cfg = dataclasses.replace(get_smoke_config(ARCH),
                              param_dtype="float32",
                              compute_dtype="float32")
    api = build(cfg)
    return api, api.init(jax.random.PRNGKey(0))


def _prompts(n, rng=None, lo=2, hi=10):
    rng = rng or np.random.default_rng(7)
    return [rng.integers(1, 64, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# dense vs paged numerical equivalence
# ---------------------------------------------------------------------------

def test_paged_decode_logits_match_dense_f32(smoke_f32):
    """Per-step decode logits agree with the dense cache path to f32
    tolerance (the paged read is the same computation re-laid-out)."""
    api, params = smoke_f32
    cfg = api.cfg
    page, max_seq = 4, 16
    maxp = max_seq // page
    B, sp = 2, 8
    toks = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, size=(B, sp)), jnp.int32)

    _, cache_d = tf.lm_prefill(params, cfg, {"tokens": toks}, max_seq)
    n_pages = B * maxp
    cache_p = tf.lm_paged_decode_init(params, cfg, n_pages + 1, page)
    npp = sp // page
    # each slot's full worst case pre-assigned (the engine grows tables
    # lazily, but attention only reads positions <= pos either way)
    table = jnp.arange(B * maxp, dtype=jnp.int32).reshape(B, maxp)
    _, cache_p = tf.lm_paged_prefill(params, cfg, {"tokens": toks}, cache_p,
                                     table[:, :npp])

    pos = jnp.full((B,), sp, jnp.int32)
    cur = toks[:, -1:]
    for _ in range(4):
        lg_d, cache_d = api.decode_step(params, cache_d, cur, pos)
        lg_p, cache_p = tf.lm_paged_decode_step(params, cfg, cache_p, cur,
                                                pos, table)
        np.testing.assert_allclose(np.asarray(lg_d[:, -1]),
                                   np.asarray(lg_p[:, -1]),
                                   atol=1e-5, rtol=1e-5)
        cur = jnp.argmax(lg_d[:, -1], axis=-1).astype(jnp.int32)[:, None]
        pos = pos + 1


def test_engine_dense_paged_tokens_equal(smoke):
    """Greedy decode through the engine is token-identical across layouts,
    at the configs' default (bfloat16) dtypes."""
    api, params = smoke
    prompts = _prompts(5)
    outs = {}
    for paged in (False, True):
        eng = ServeEngine(api, params, n_slots=2, max_seq=64, paged=paged)
        reqs = [eng.submit(p, max_new=6) for p in prompts]
        eng.run()
        assert all(r.done for r in reqs)
        outs[paged] = [r.out for r in reqs]
    assert outs[False] == outs[True]


# ---------------------------------------------------------------------------
# slot refill / retirement / page lifecycle
# ---------------------------------------------------------------------------

def test_slot_refill_matches_solo(smoke):
    """More requests than slots: refilled slots produce the same tokens as
    solo runs (no state leaks across waves), over multiple prefill waves."""
    api, params = smoke
    prompts = _prompts(5, np.random.default_rng(3))
    news = [3, 7, 4, 6, 5]
    solo = []
    for p, n in zip(prompts, news):
        eng = ServeEngine(api, params, n_slots=1, max_seq=64, paged=True)
        r = eng.submit(p, max_new=n)
        eng.run()
        solo.append(r.out)
    eng = ServeEngine(api, params, n_slots=2, max_seq=64, paged=True)
    reqs = [eng.submit(p, max_new=n) for p, n in zip(prompts, news)]
    eng.run()
    assert eng.report()["prefill_calls"] >= 2     # multiple admission waves
    for r, want in zip(reqs, solo):
        assert r.done and r.out == want, (r.out, want)


def test_eos_retirement(smoke):
    api, params = smoke
    prompt = [3, 1, 4, 1, 5]
    ref = ServeEngine(api, params, n_slots=1, max_seq=64, paged=True)
    r0 = ref.submit(prompt, max_new=8)
    ref.run()
    eos = r0.out[1]               # eos is only checked on decode ticks
    stop = next(i for i in range(1, len(r0.out)) if r0.out[i] == eos)

    eng = ServeEngine(api, params, n_slots=1, max_seq=64, paged=True,
                      eos_id=eos)
    r = eng.submit(prompt, max_new=20)
    eng.run()
    assert r.done and r.out == r0.out[:stop + 1]


def test_max_new_and_max_seq_retirement(smoke):
    api, params = smoke
    eng = ServeEngine(api, params, n_slots=2, max_seq=32, paged=True,
                      page_size=16)
    short = eng.submit([1, 2, 3], max_new=3)
    capped = eng.submit(list(range(1, 29)), max_new=100)   # hits max_seq
    eng.run()
    assert short.done and len(short.out) == 3
    assert capped.done and len(capped.out) < 100
    assert len(capped.prompt) + len(capped.out) <= 32


def test_page_free_and_reuse(smoke):
    api, params = smoke
    eng = ServeEngine(api, params, n_slots=2, max_seq=32, paged=True,
                      page_size=8)
    assert eng.n_pages == 8
    reqs = [eng.submit(p, max_new=4) for p in _prompts(2)]
    eng.step()
    first = {pid for pages in eng._slot_pages for pid in pages}
    assert first and eng._trash not in first
    assert eng.pool_occupancy() == pytest.approx(len(first) / eng.n_pages)
    eng.run()
    assert all(r.done for r in reqs)
    assert sorted(eng._free) == list(range(eng.n_pages))   # all freed
    assert (eng._table_np == eng._trash).all()

    reqs2 = [eng.submit(p, max_new=4) for p in _prompts(2)]
    eng.step()
    second = {pid for pages in eng._slot_pages for pid in pages}
    assert first & second                                  # pages reused
    eng.run()
    assert all(r.done for r in reqs2)


def test_stall_and_resume(smoke):
    """A slot that cannot grow (empty free list) stalls with its state
    intact and resumes — producing the same tokens — once pages free up."""
    api, params = smoke
    prompt = [5, 6, 7]
    ref = ServeEngine(api, params, n_slots=2, max_seq=32, paged=True,
                      page_size=4)
    r_ref = ref.submit(prompt, max_new=10)
    ref.run()

    eng = ServeEngine(api, params, n_slots=2, max_seq=32, paged=True,
                      page_size=4)
    r = eng.submit(prompt, max_new=10)
    eng.step()                                   # admit: 1 page in use
    stolen, eng._free = eng._free, []            # pool "exhausted"
    for _ in range(8):
        eng.step()
        if eng._stalled.any():
            break
    assert eng._stalled[0] and not eng.active[0] and not r.done
    eng._free = stolen
    eng.run()
    assert r.done and r.out == r_ref.out


def test_partial_resume_syncs_page_table(smoke):
    """Fewer free pages than stalled slots: the slots that DO resume must
    have their new page pushed to the device table before the next tick
    (regression: an early return skipped the sync, so the resumed slot's
    KV scattered into the trash page — silent corruption). Tokens must
    match the dense engine exactly."""
    api, params = smoke
    prompts = [[5, 6, 7], [9, 2, 4]]
    ref = ServeEngine(api, params, n_slots=2, max_seq=32, paged=False)
    refs = [ref.submit(p, max_new=8) for p in prompts]
    ref.run()

    eng = ServeEngine(api, params, n_slots=2, max_seq=32, paged=True,
                      page_size=4)
    reqs = [eng.submit(p, max_new=8) for p in prompts]
    eng.step()                    # admit wave + tick 1 (grows to 2 pages)
    stolen, eng._free = eng._free, []            # pool "exhausted"
    for _ in range(10):                          # both outgrow page 2
        if eng._stalled.all():
            break
        eng.step()
    assert eng._stalled.all() and not any(r.done for r in reqs)
    eng._free = [stolen.pop()]                   # 1 page for 2 stalled slots
    eng.step()
    assert eng.active[0] and eng._stalled[1]     # partial resume
    # the resumed slot's new page must be on DEVICE, not just in the host
    # mirror — a stale device row scatters its KV into the trash page
    np.testing.assert_array_equal(np.asarray(eng.page_table), eng._table_np)
    eng.run()                # slot 0 retires -> its pages resume slot 1
    assert all(r.done for r in reqs)
    assert [r.out for r in reqs] == [r.out for r in refs]


def test_pool_exhaustion_raises(smoke):
    """Every in-flight request stalled with nothing retirable is a
    deadlock: the engine must fail loudly, not spin."""
    api, params = smoke
    eng = ServeEngine(api, params, n_slots=2, max_seq=16, paged=True,
                      page_size=4, n_pages=4)
    for p in _prompts(2, lo=2, hi=4):
        eng.submit(p, max_new=14)               # both need all 4 pages
    with pytest.raises(RuntimeError, match="page pool exhausted"):
        eng.run()


def test_pool_below_single_request_rejected(smoke):
    api, params = smoke
    with pytest.raises(ValueError, match="pool smaller"):
        ServeEngine(api, params, n_slots=1, max_seq=32, paged=True,
                    page_size=4, n_pages=2)


# ---------------------------------------------------------------------------
# engine bookkeeping fixes
# ---------------------------------------------------------------------------

def test_uids_monotonic_never_reused(smoke):
    api, params = smoke
    eng = ServeEngine(api, params, n_slots=1, max_seq=64)
    a = eng.submit([1, 2], max_new=2)
    eng.run()
    b = eng.submit([3, 4], max_new=2)            # queue drained and refilled
    c = eng.submit([5, 6], max_new=2)
    assert (a.uid, b.uid, c.uid) == (a.uid, a.uid + 1, a.uid + 2)


def test_run_returns_late_and_stepped_completions(smoke):
    """run() completions cover requests finished by manual step() calls and
    requests submitted after a previous run — not a startup snapshot."""
    api, params = smoke
    eng = ServeEngine(api, params, n_slots=1, max_seq=64)
    a = eng.submit([1, 2, 3], max_new=2)
    while not a.done:
        eng.step()
    b = eng.submit([4, 5], max_new=2)
    done = eng.run()
    assert {r.uid for r in done} == {a.uid, b.uid}
    assert eng.run() == []                       # drained


def test_scatter_slot_respects_declared_axes():
    """Only leaves whose cache_spec declares a "cache_batch" axis are
    scattered, on THAT axis; shared leaves (no batch axis) pass through."""
    spec = {"kv": (None, "cache_batch", "cache_seq"), "kpos": ("cache_seq",)}
    fake = SimpleNamespace(api=SimpleNamespace(cache_spec=lambda: spec))
    big = {"kv": jnp.zeros((2, 4, 6)), "kpos": jnp.arange(6.0)}
    small = {"kv": jnp.ones((2, 1, 6)), "kpos": jnp.full((6,), 9.0)}
    out = ServeEngine._scatter_slot(fake, big, small, 2)
    kv = np.asarray(out["kv"])
    assert (kv[:, 2] == 1).all() and kv.sum() == 12      # axis 1, slot 2 only
    np.testing.assert_array_equal(np.asarray(out["kpos"]), np.arange(6.0))


# ---------------------------------------------------------------------------
# fleet "serve" target kind
# ---------------------------------------------------------------------------

def test_serve_plan_roundtrip_and_names(tmp_path):
    from repro.fleet.plan import SweepPlan, TargetSpec

    spec = TargetSpec("serve", ("fp_add32",),
                      {"arch": ARCH, "slots": 2, "prompt": 8, "max_new": 4})
    plan = SweepPlan(name="t", store=str(tmp_path / "s.jsonl"),
                     targets=[spec], reps=1)
    plan.validate()
    names = spec.region_names()
    assert len(names) == 2
    assert any("prefill" in n for n in names)
    assert any("decode" in n for n in names)
    path = str(tmp_path / "plan.json")
    plan.save(path)
    again = SweepPlan.load(path)
    assert again.targets[0].kind == "serve"
    assert again.digest() == plan.digest()
    assert again.grid() == plan.grid()


def test_serve_plan_validation_rejects_bad_params(tmp_path):
    from repro.fleet.plan import PlanError, SweepPlan, TargetSpec

    with pytest.raises(PlanError, match="slots"):
        SweepPlan(name="t", store="s", targets=[
            TargetSpec("serve", ("fp_add32",), {"arch": ARCH, "slots": 0})
        ]).validate()
    with pytest.raises(PlanError, match="arch"):
        SweepPlan(name="t", store="s", targets=[
            TargetSpec("serve", ("fp_add32",), {})   # arch missing
        ]).validate()


def test_serve_campaign_classifies_and_replays(tmp_path):
    """The acceptance path: a fleet run over a "serve" plan classifies
    prefill and decode as separate regions into a resumable store, and a
    completed campaign replays with ZERO new measurements."""
    from repro.fleet.executor import run_worker
    from repro.fleet.plan import SweepPlan, TargetSpec

    plan = SweepPlan(
        name="serve-test", store=str(tmp_path / "serve.jsonl"),
        targets=[TargetSpec("serve", ("fp_add32",),
                            {"arch": "gemma_2b", "slots": 2, "prompt": 8,
                             "max_new": 4})],
        reps=1)
    plan.validate()
    reports, stats = run_worker(plan, fresh=True)
    assert stats.measured > 0
    names = sorted(reports)
    assert len(names) == 2
    assert any("prefill" in n for n in names)
    assert any("decode" in n for n in names)
    for rep in reports.values():
        assert rep.bottleneck.label            # classified, not empty

    reports2, stats2 = run_worker(plan, expect_no_measure=True)
    assert stats2.measured == 0 and stats2.cached > 0
    assert sorted(reports2) == names


# ---------------------------------------------------------------------------
# a prompt that fills its bucket; the engine against a plain reference
# ---------------------------------------------------------------------------

WITNESS_SEED = 2 ** 33 + 1


@pytest.mark.parametrize("prompt_len", [16, 32])
def test_a_bucket_filling_prompt_keeps_its_first_decode_position(smoke_f32,
                                                                 prompt_len):
    """A prompt whose length is its bucket (16, 32 with pages of 16): the
    page holding position len(prompt) is allocated at admission, so the
    first tick writes its key and value to the slot's own page, not the
    trash page, and every served token is the full forward's argmax."""
    api, _ = smoke_f32
    cfg = api.cfg
    params = api.init(jax.random.PRNGKey(WITNESS_SEED))
    eng = ServeEngine(api, params, n_slots=2, max_seq=64, paged=True,
                      page_size=16)
    rng = np.random.default_rng(WITNESS_SEED)
    r = eng.submit(rng.integers(1, cfg.vocab_size, size=prompt_len).tolist(),
                   max_new=12)
    assert eng.admit()
    assert eng._table_np[0, prompt_len // 16] != eng._trash
    assert len(eng._slot_pages[0]) == prompt_len // 16 + 1
    eng.run()
    logits, _ = tf.lm_forward(params, cfg,
                              {"tokens": jnp.asarray([r.prompt
                                                      + r.out[:-1]])})
    want = np.argmax(np.asarray(logits[0, prompt_len - 1:]), axis=-1)
    assert r.out == want.tolist()


def _tiny_dsc(**over):
    """DeepSeek-Coder's shape at tiny widths: GQA 7:1, RoPE theta 1e5 with
    linear scaling x4, RMSNorm eps 1e-6, untied head, 2 layers, float32."""
    from repro.configs import ModelConfig

    return ModelConfig(**{
        "name": "tiny-dsc", "family": "dense", "n_layers": 2, "d_model": 64,
        "n_heads": 14, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
        "vocab_size": 256, "norm_eps": 1e-6, "rope_theta": 1e5,
        "rope_scaling": 4.0, "param_dtype": "float32",
        "compute_dtype": "float32", **over})


def _reference_weights(params):
    lay = params["layers"]
    roles = {"attn_norm": lay["ln1"]["scale"], "wq": lay["attn"]["wq"],
             "wk": lay["attn"]["wk"], "wv": lay["attn"]["wv"],
             "wo": lay["attn"]["wo"], "mlp_norm": lay["ln2"]["scale"],
             "w_gate": lay["mlp"]["w_gate"], "w_up": lay["mlp"]["w_up"],
             "w_down": lay["mlp"]["w_down"]}
    n = lay["ln1"]["scale"].shape[0]
    return {"embed": params["embed"]["table"],
            "head": params["embed"]["head"],
            "final_norm": params["final_norm"]["scale"],
            "layers": [{k: v[i] for k, v in roles.items()}
                       for i in range(n)]}


# max|logits - reference| / max|reference| of the float32 engine against the
# float32 reference (both at highest matmul precision on the CPU)
REFERENCE_TOL = 1e-4


def test_paged_engine_logits_match_the_plain_reference():
    """Prefill, then 6 decode ticks of the paged engine, a bucket-filling
    prompt among the four: each tick's logits agree with the plain float32
    forward of ``chipbench/reference/decoder.py`` over the slot's prompt
    and the tokens served so far, and every served token is its argmax."""
    from chipbench.reference import decoder as ref

    cfg = _tiny_dsc()
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(5))
    eng = ServeEngine(api, params, n_slots=4, max_seq=64, paged=True,
                      page_size=8)
    rng = np.random.default_rng(5)
    reqs = [eng.submit(rng.integers(1, cfg.vocab_size, size=n).tolist(),
                       max_new=8) for n in (32, 21, 13, 7)]
    weights = _reference_weights(params)
    assert eng.admit()
    tick = jax.jit(eng.probe_cells()[2])
    with jax.default_matmul_precision("highest"):
        for _ in range(6):
            logits = np.asarray(tick(*eng.probe_cells()[3])[4])
            want = ref.last_logits(weights, [r.prompt + r.out for r in reqs],
                                   norm_eps=cfg.norm_eps,
                                   rope_theta=cfg.rope_theta,
                                   rope_factor=cfg.rope_scaling)
            assert ref.max_rel_err(logits, want) < REFERENCE_TOL
            eng.step()
            assert [r.out[-1] for r in reqs] == \
                np.argmax(want, axis=-1).tolist()


def test_rope_angles_at_factor_1_are_bit_identical():
    """Linear scaling at its default factor computes the angles exactly as
    before it existed; at factor 4 they are those of positions / 4."""
    from repro.models.layers import rope_angles

    pos = jnp.arange(0, 4096, 7, dtype=jnp.int32)
    half = 64
    freqs = 1.0 / (1e5 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * freqs
    for got, want in zip(rope_angles(pos, 128, 1e5),
                         (jnp.cos(ang), jnp.sin(ang))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ang4 = (pos.astype(jnp.float32) / 4.0)[..., None] * freqs
    for got, want in zip(rope_angles(pos, 128, 1e5, 4.0),
                         (jnp.cos(ang4), jnp.sin(ang4))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_published_deepseek_coder_config():
    from repro.configs import get_config

    cfg = get_config("deepseek-coder-33b")
    assert (cfg.norm_eps, cfg.rope_theta, cfg.rope_scaling) == \
        (1e-6, 1e5, 4.0)
    assert get_config("gemma-2b").rope_scaling == 1.0


# ---------------------------------------------------------------------------
# the serve kind at published widths (a tiny stand-in config on the CPU)
# ---------------------------------------------------------------------------

PUBLISHED = {"arch": "tiny-dsc", "layers": 2, "slots": 4, "max_seq": 64,
             "page_size": 8, "prompt_lens": [32, 21, 13, 7], "max_new": 8,
             "regions": ["decode"], "seed": 7}


@pytest.fixture
def tiny_arch(monkeypatch):
    """Let ``get_config("tiny-dsc")`` find the tiny stand-in; free every
    engine the test built."""
    import repro.configs as configs
    from repro.serve.load import release_serve_engines

    get_config = configs.get_config
    monkeypatch.setattr(configs, "get_config", lambda name: _tiny_dsc(
        n_layers=3) if name == "tiny-dsc" else get_config(name))
    yield
    release_serve_engines()


def test_published_serve_plan_roundtrip(tmp_path, tiny_arch):
    from repro.fleet.plan import SweepPlan, TargetSpec

    plan = SweepPlan(name="t", store=str(tmp_path / "s.jsonl"),
                     targets=[TargetSpec("serve", ("hbm_stream", "fp_add32"),
                                         dict(PUBLISHED))], reps=1)
    path = plan.save(str(tmp_path / "plan.json"))
    again = SweepPlan.load(path)
    assert again.targets[0].params == PUBLISHED
    assert again.digest() == plan.digest()
    assert again.grid() == plan.grid() == [
        ("tiny-dsc_L2_serve_decode_b4_m64_p8_s32-21-13-7_n8_r7", m)
        for m in ("hbm_stream", "fp_add32")]


@pytest.mark.parametrize("params,match", [
    ({"arch": ARCH, "prompt": 8, "pages": 2}, "unknown serve param"),
    ({**PUBLISHED, "prompt": 8}, "unknown serve param"),
    ({**PUBLISHED, "arch": "no-such-model"}, "unknown architecture"),
    ({**PUBLISHED, "layers": 0}, "layers"),
    ({**PUBLISHED, "layers": 4}, "layers=4"),
    ({**PUBLISHED, "max_seq": 60}, "multiple of page_size"),
    ({**PUBLISHED, "prompt_lens": [60]}, "exceed max_seq"),
    ({**PUBLISHED, "prompt_lens": []}, "prompt_lens"),
    ({**PUBLISHED, "prompt_lens": [4] * 5}, "prompt_lens"),
    ({**PUBLISHED, "max_new": 2}, "max_new"),
    ({**PUBLISHED, "regions": ["train"]}, "regions"),
    ({**PUBLISHED, "regions": ["decode", "decode"]}, "regions"),
    ({**PUBLISHED, "seed": -1}, "seed"),
    ({**PUBLISHED, "weights": ""}, "weights"),
    ({**PUBLISHED, "weights": 3}, "weights"),
], ids=["smoke-unknown", "published-unknown", "arch", "layers-0",
        "layers-over", "max-seq", "prompt-too-long", "no-prompts",
        "prompts-over-slots", "max-new", "region", "region-twice", "seed",
        "weights-empty", "weights-not-a-path"])
def test_serve_plan_validation_rejects_published_params(tiny_arch, params,
                                                        match):
    from repro.fleet.plan import PlanError, SweepPlan, TargetSpec

    with pytest.raises(PlanError, match=match):
        SweepPlan(name="t", store="s", targets=[
            TargetSpec("serve", ("fp_add32",), params)]).validate()


@pytest.mark.parametrize("key,value", [
    ("layers", 3), ("slots", 8), ("max_seq", 128), ("page_size", 16),
    ("prompt_lens", [32, 21, 13, 8]), ("max_new", 9), ("seed", 8),
    ("arch", "deepseek-coder-33b"), ("weights", "/ckpt/a")])
def test_published_region_names_differ_in_every_engine_param(tiny_arch,
                                                             key, value):
    from repro.fleet.plan import TargetSpec

    def names(p):
        return TargetSpec("serve", ("fp_add32",), p).region_names()

    assert set(names(PUBLISHED)).isdisjoint(names({**PUBLISHED,
                                                   key: value}))


def test_smoke_serve_region_names_are_unchanged():
    from repro.fleet.plan import TargetSpec

    spec = TargetSpec("serve", ("fp_add32",),
                      {"arch": ARCH, "slots": 2, "prompt": 8, "max_new": 4})
    assert spec.region_names() == [
        "deepseek-coder-33b-smoke_serve_prefill_s8_n4_p16_b2",
        "deepseek-coder-33b-smoke_serve_decode_s8_n4_p16_b2"]


def test_published_serve_target_shares_one_engine_until_released(tiny_arch):
    """Plans with the same serve params resolve to regions over one engine
    (a campaign resolves its plan in several places); releasing it frees
    the engine's arrays, and the next resolve builds a new one."""
    from repro.fleet.plan import SweepPlan, TargetSpec
    from repro.serve.load import probed_engine

    def plan():
        return SweepPlan(name="t", store="s", targets=[
            TargetSpec("serve", ("fp_add32",), dict(PUBLISHED))])

    a, b = plan(), plan()
    (ra,), (rb,) = a.resolve()[0][1], b.resolve()[0][1]
    eng = probed_engine(PUBLISHED)
    assert ra.args_for_rt("fp_add32")[1] is eng.params
    assert rb.args_for_rt("fp_add32")[1] is eng.params
    assert [len(r.prompt) + len(r.out) for r in eng.slot_req] == \
        [n + 3 for n in PUBLISHED["prompt_lens"]]
    weights = jax.tree.leaves(eng.params)
    a.release()
    assert all(w.is_deleted() for w in weights)
    again = probed_engine(PUBLISHED)
    assert again is not eng
    # another target's engine takes the place of the one before
    other = probed_engine({**PUBLISHED, "seed": 8})
    assert all(w.is_deleted() for w in jax.tree.leaves(again.params))
    assert not any(w.is_deleted() for w in jax.tree.leaves(other.params))


def _save_leaves(tree, directory):
    from repro.ckpt import leaf_name

    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(x)
        if x.dtype.kind == "V":              # bfloat16: its bits
            x = x.view(f"u{x.dtype.itemsize}")
        np.save(os.path.join(directory, f"{leaf_name(path)}.npy"), x)


def test_published_engine_reads_its_weights_from_a_directory(tiny_arch,
                                                            tmp_path):
    """With ``weights`` the engine holds the files' arrays, not the ones
    ``seed`` would draw; prompts still come from ``seed``."""
    from repro.configs import get_config
    from repro.serve.load import probed_engine

    api = build(get_config("tiny-dsc").scaled(n_layers=2))
    saved = api.init(jax.random.PRNGKey(11))
    _save_leaves(saved, tmp_path)
    drawn = probed_engine(PUBLISHED)
    drawn_wq = np.asarray(drawn.params["layers"]["attn"]["wq"])
    prompts = [r.prompt for r in drawn.slot_req]
    eng = probed_engine({**PUBLISHED, "weights": str(tmp_path)})
    for got, want in zip(jax.tree.leaves(eng.params),
                         jax.tree.leaves(saved), strict=True):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(eng.params["layers"]["attn"]["wq"], drawn_wq)
    assert [r.prompt for r in eng.slot_req] == prompts


def test_load_weights_reads_bfloat16_bits_and_casts_nothing(tmp_path):
    from repro.serve.load import load_weights

    tree = {"a": {"w": jax.random.normal(jax.random.PRNGKey(0), (3, 5),
                                         jnp.bfloat16)},
            "b": jnp.arange(4, dtype=jnp.float32)}
    _save_leaves(tree, tmp_path)
    like = jax.eval_shape(lambda: tree)
    got = load_weights(str(tmp_path), like)
    assert got["a"]["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got["a"]["w"]),
                                  np.asarray(tree["a"]["w"]))
    np.testing.assert_array_equal(np.asarray(got["b"]), np.arange(4))
    np.save(tmp_path / "b.npy", np.arange(4, dtype=np.float64))
    with pytest.raises(ValueError, match="b: file holds float64"):
        load_weights(str(tmp_path), like)
    np.save(tmp_path / "b.npy", np.arange(5, dtype=np.float32))
    with pytest.raises(ValueError, match=r"b: file holds \(5,\)"):
        load_weights(str(tmp_path), like)


def test_published_decode_region_payload_check_holds_outputs(tiny_arch):
    """The published serve region's payload check counts the patterns of
    the static build at min(k, 16), and finds the noisy builds' outputs
    bit-identical to the tick's at k=0."""
    from repro.core import Controller
    from repro.fleet.plan import TargetSpec

    (region,) = TargetSpec("serve", ("fp_add32",),
                           dict(PUBLISHED)).resolve()
    rep = Controller(reps=1).verify_mode_payload(region, "fp_add32",
                                                 [0, 5, 10, 20])
    assert rep.expected == 16 and rep.ok()
    assert rep.ref_err == 0.0 and rep.ref_tol == 0.0


def test_fleet_plan_cli_writes_a_published_serve_target(tmp_path):
    from repro.fleet.cli import main
    from repro.fleet.plan import SweepPlan

    path = str(tmp_path / "p.json")
    main(["plan", "--out", path, "--serve", "--arch", "deepseek-coder-33b",
          "--layers", "8", "--batch", "4", "--prompt-lens",
          "2048,1531,1109,742", "--max-new", "64", "--regions", "decode",
          "--seed", "11", "--modes", "hbm_stream,fp_add32",
          "--store", str(tmp_path / "s.jsonl"), "--shards", "1"])
    (spec,) = SweepPlan.load(path).targets
    assert spec.params == {"arch": "deepseek-coder-33b", "layers": 8,
                           "slots": 4, "max_seq": 4096, "page_size": 16,
                           "prompt_lens": [2048, 1531, 1109, 742],
                           "max_new": 64, "regions": ["decode"], "seed": 11}
    assert spec.region_names() == [
        "deepseek-coder-33b_L8_serve_decode_b4_m4096_p16"
        "_s2048-1531-1109-742_n64_r11"]
