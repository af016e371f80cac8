"""Bring-up smoke of the noise-injection analyser on a TPU.

    python chip_smoke.py                # one chip: the three phases below
    python chip_smoke.py --chips 4      # four chips: the ICI phase only

One process does all of it (a chip belongs to one process at a time):

1. kernel campaign: a SweepPlan of the paper's Pallas kernels at real size,
   run through ``fleet run`` with ``backend="pallas"`` — matmul n=4096
   (fp/mxu/vmem), SPMXV with 2^18 rows and 16 nonzeros per row at q=0 and
   q=1 (fp/vmem), flash attention at Gemma-2B's attention shape (batch 1,
   8 heads, 1 kv head, seq 4096, head_dim 256). Every pair's payload check
   must pass, and each kernel's main output must match its float32
   reference within ``repro.kernels.region.REF_TOL``.
2. Gemma-2B serve: the full-width config with random weights from
   ``--seed``, a paged ``ServeEngine`` with 4 slots answering 8 requests
   (prompts of 16 to 128 tokens, 16 new tokens each). A float32 forward
   of each prompt and its served tokens is the reference: the first token
   the engine served must be its argmax, the prefill's first-token logits
   must lie within ``GEMMA_LOGIT_TOL`` of it, and every decoded token must
   sit within ``TOKEN_MARGIN`` of its position's float32 maximum.
3. decode-tick probe: the engine's decode tick wrapped with graph-level
   noise (``core.injector.step_region``), ``hbm_stream`` and ``fp_add32``
   swept over a short k grid by the ``Controller``; both payloads must
   verify.

``--chips 4`` builds a (1, 4) mesh with a ``model`` axis and sweeps
``ici_allreduce``, ``ici_allgather`` and ``ici_a2a`` around a small
tensor-parallel step; each collective's result is checked against NumPy and
its compiled HLO must hold the collective.

The script refuses to start without a TPU or with a ``REPRO_SYNTH_*`` /
``REPRO_NOISE_SABOTAGE`` variable set. Any failed phase exits non-zero. The
last line of standard output is one JSON object naming the device; it is
printed only when every phase passed. Plans, stores and reports go under
``--out`` (default ``chiprun_out/chip_smoke``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# the real sizes of the one-chip kernel campaign (name -> plan target)
KERNEL_TARGETS = {
    "matmul": {"kernel": "matmul", "sizes": [4096],
               "modes": ["fp", "mxu", "vmem"]},
    "spmxv": {"kernel": "spmxv", "sizes": [1 << 18], "qs": [0.0, 1.0],
              "nnz_per_row": 16, "modes": ["fp", "vmem"]},
    "attention": {"kernel": "attention", "sizes": [4096], "batch": 1,
                  "heads": 8, "kv_heads": 1, "head_dim": 256,
                  "modes": ["fp", "mxu", "vmem"]},
}
KERNEL_REPS = 2
# the serve workload
N_SLOTS, N_REQUESTS, MAX_NEW = 4, 8, 16
PROMPT_LENS = (16, 128)              # inclusive range of prompt lengths
MAX_SEQ, PAGE_SIZE = 256, 16
# max|logits - ref| / max|ref| of bf16 serving against the f32 forward
GEMMA_LOGIT_TOL = 5e-2
# how far below the f32 maximum (over max|logit|) a served token's f32
# logit may sit: bf16 and f32 may each be off by the tolerance at a near-tie
TOKEN_MARGIN = 2 * GEMMA_LOGIT_TOL
DECODE_MODES = ("hbm_stream", "fp_add32")
DECODE_KS = (0, 4, 16, 64)
PROBE_REPS = 3                       # decode-tick and ICI sweeps
ICI_MODES = ("ici_allreduce", "ici_allgather", "ici_a2a")
ICI_KS = (0, 2, 4, 8)
ICI_MLP = (1024, 4096, 64)           # d_model, d_ff, batch of the TP step
# the HLO opcode each ICI mode must compile to
ICI_OPCODE = {"ici_allreduce": "all-reduce", "ici_allgather": "all-gather",
              "ici_a2a": "all-to-all"}


class SmokeError(RuntimeError):
    """A phase failed its check."""


class CompileClock:
    """Seconds JAX spends lowering and compiling, summed from its monitoring
    events (tracing is left out: its events nest); what remains of a phase
    is tracing, running and measuring."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration

    @contextlib.contextmanager
    def phase(self, name: str, out: dict):
        c0, t0 = self.total, time.perf_counter()
        try:
            yield
        finally:
            wall = time.perf_counter() - t0
            comp = self.total - c0
            out[name] = {"wall_s": wall, "compile_s": comp,
                         "run_s": wall - comp}
            print(f"[phase {name}: {wall:.1f} s, compile {comp:.1f} s, "
                  f"run/measure {wall - comp:.1f} s]", flush=True)


def refuse_unless_clean_env() -> None:
    bad = sorted(k for k in os.environ
                 if k.startswith("REPRO_SYNTH_") or k == "REPRO_NOISE_SABOTAGE")
    if bad:
        raise SystemExit(f"chip_smoke: refusing to run with {bad} set — "
                         "those are test seams that fake the clock or "
                         "sabotage the noise")


def require_tpu(n_chips: int):
    import jax

    dev = jax.devices()
    if dev[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX finds "
                         f"{dev[0].platform!r} ({dev[0].device_kind})")
    if len(dev) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"devices; JAX finds {len(dev)}")
    return dev


# ---------------------------------------------------------------------------
# phase 1: the kernel campaign through `fleet run`
# ---------------------------------------------------------------------------

def kernel_campaign(out_dir: Path, clock: CompileClock) -> dict:
    """One fleet plan per kernel family, each run fresh through
    ``run_fleet``; returns {family: {region: per-mode payload results}}."""
    from repro.fleet.executor import run_fleet
    from repro.fleet.plan import SweepPlan, TargetSpec

    out: dict = {}
    for family, spec in KERNEL_TARGETS.items():
        params = {k: v for k, v in spec.items() if k != "modes"}
        plan = SweepPlan(name=f"chip_smoke_{family}",
                         store=str(out_dir / f"{family}.jsonl"),
                         targets=[TargetSpec("pallas", tuple(spec["modes"]),
                                             params)],
                         reps=KERNEL_REPS, shards=1, backend="pallas")
        path = plan.save(str(out_dir / f"{family}.plan.json"))
        times: dict = {}
        with clock.phase(f"kernels/{family}", times):
            res = run_fleet(path, fresh=True)
        regions = {}
        for name, rep in sorted(res.reports.items()):
            modes = {}
            for mode, r in rep.results.items():
                inj = r.injection
                if inj is None or not inj.ok():
                    raise SmokeError(f"{name}/{mode}: payload not verified "
                                     f"({inj})")
                modes[mode] = {"payload": f"{inj.payload}/{inj.expected}",
                               "ref_err": inj.ref_err,
                               "ref_tol": inj.ref_tol,
                               "abs_raw": r.fit.k1}
                print(f"  {name}/{mode}: payload {inj.payload}/"
                      f"{inj.expected}, main output vs f32 reference "
                      f"{inj.ref_err:.3g} (tol {inj.ref_tol:g}), "
                      f"Abs^raw {r.fit.k1:.1f}", flush=True)
            print(f"  {name} => {rep.bottleneck}", flush=True)
            regions[name] = {"verdict": rep.bottleneck.label,
                             "modes": modes}
        out[family] = {"regions": regions, **times[f"kernels/{family}"]}
    return out


# ---------------------------------------------------------------------------
# phase 2: Gemma-2B serve against a float32 forward
# ---------------------------------------------------------------------------

def gemma_serve(cfg, clock: CompileClock, *, seed: int) -> tuple:
    """Serve ``N_REQUESTS`` random prompts on a paged engine and hold what
    it served to a float32 forward. Returns (summary, decode-tick cell
    snapshot)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as tf
    from repro.models.model import build
    from repro.serve.engine import ServeEngine

    times: dict = {}
    api = build(cfg)
    with clock.phase("serve/init", times):
        params = jax.jit(api.init)(jax.random.PRNGKey(seed))
        jax.block_until_ready(params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"  {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {n_params / 1e9:.3f} B params "
          f"({cfg.param_dtype})", flush=True)

    cfg32 = cfg.scaled(compute_dtype="float32")

    @jax.jit
    def prefill_logits(p, cache, toks, rows, lens):
        # the forward of the engine's batched prefill, which returns only
        # the sampled tokens, with its last-position logits kept
        logits, _ = tf.lm_paged_prefill(p, cfg, {"tokens": toks}, cache,
                                        rows)
        return jnp.take_along_axis(
            logits, (lens - 1)[:, None, None], axis=1)[:, 0]

    @jax.jit
    def ref_logits(p, toks, first):
        # float32 forward of prompt + served tokens (teacher forcing): the
        # logits that chose each of the MAX_NEW served tokens
        logits, _ = tf.lm_forward(p, cfg32, {"tokens": toks})
        at = first[:, None] + jnp.arange(MAX_NEW)[None, :]
        return jnp.take_along_axis(logits, at[:, :, None], axis=1)

    eng = ServeEngine(api, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                      page_size=PAGE_SIZE, seed=seed)
    rng = np.random.default_rng(seed)
    reqs = [eng.submit(rng.integers(1, cfg.vocab_size,
                                    size=int(rng.integers(
                                        PROMPT_LENS[0], PROMPT_LENS[1] + 1))
                                    ).tolist(), max_new=MAX_NEW)
            for _ in range(N_REQUESTS)]

    served: dict = {}                   # id(request) -> prefill logits
    waves, seen, tick_cell, ticks_since = 0, None, None, 0
    with clock.phase("serve/run", times):
        for _ in range(10 * N_REQUESTS * MAX_NEW):
            if not eng.queue and all(r is None for r in eng.slot_req):
                break
            eng.step()
            _, pf_args, tk_fn, tk_args = eng.probe_cells()
            _, cache, toks, rows, lens, adm = pf_args[:6]
            if toks is not seen:                    # a new admission wave
                seen, waves, ticks_since = toks, waves + 1, 0
                got = np.asarray(prefill_logits(params, cache, toks, rows,
                                                lens), np.float64)
                for b in np.flatnonzero(np.asarray(adm)):
                    served[id(eng.slot_req[b])] = got[b]
            else:
                ticks_since += 1
            if waves == 2 and ticks_since == 2 and tick_cell is None:
                tick_cell = (tk_fn, tk_args)    # mid-decode, all slots live
    done = [r for r in reqs if r.done]
    if len(done) != N_REQUESTS or any(len(r.out) != MAX_NEW for r in reqs):
        raise SmokeError(f"serve: {len(done)}/{N_REQUESTS} requests done, "
                         f"lengths {[len(r.out) for r in reqs]}")
    if sorted(served) != sorted(id(r) for r in reqs):
        raise SmokeError(f"serve: prefill logits of {len(served)} requests "
                         f"captured, want {N_REQUESTS}")

    with clock.phase("serve/reference", times):
        want = []
        for i in range(0, N_REQUESTS, N_SLOTS):
            batch = reqs[i:i + N_SLOTS]
            toks = np.zeros((len(batch), PROMPT_LENS[1] + MAX_NEW), np.int32)
            for b, r in enumerate(batch):
                seq = r.prompt + r.out[:-1]
                toks[b, :len(seq)] = seq
            first = np.array([len(r.prompt) - 1 for r in batch], np.int32)
            with jax.default_matmul_precision("highest"):
                want.extend(np.asarray(ref_logits(params, toks, first),
                                       np.float64))

    logit_errs, first_agree, tok_agree, gaps = [], 0, 0, []
    for r, w in zip(reqs, want):        # w: (MAX_NEW, vocab)
        logit_errs.append(float(np.max(np.abs(served[id(r)] - w[0]))
                                / np.max(np.abs(w[0]))))
        first_agree += int(r.out[0] == int(np.argmax(w[0])))
        for j, tok in enumerate(r.out):
            tok_agree += int(tok == int(np.argmax(w[j])))
            gaps.append(float((np.max(w[j]) - w[j][tok])
                              / np.max(np.abs(w[j]))))
    worst, worst_gap = max(logit_errs), max(gaps)
    print(f"  {N_REQUESTS}/{N_REQUESTS} requests answered in {waves} "
          f"admission waves, {eng.stats['ticks']} decode ticks; against a "
          f"float32 forward: first token = argmax on {first_agree}/"
          f"{N_REQUESTS}, prefill logits max normalized error {worst:.4g} "
          f"(tol {GEMMA_LOGIT_TOL:g}), all served tokens = argmax on "
          f"{tok_agree}/{len(gaps)}, largest gap below the maximum "
          f"{worst_gap:.4g} (margin {TOKEN_MARGIN:g})", flush=True)
    if first_agree != N_REQUESTS:
        raise SmokeError(f"serve: the first served token is the f32 argmax "
                         f"on only {first_agree}/{N_REQUESTS} requests")
    if worst > GEMMA_LOGIT_TOL:
        raise SmokeError(f"serve: prefill logits differ from the f32 forward "
                         f"by {worst:.4g} > {GEMMA_LOGIT_TOL:g}")
    if worst_gap > TOKEN_MARGIN:
        raise SmokeError(f"serve: a served token sits {worst_gap:.4g} below "
                         f"the f32 maximum > {TOKEN_MARGIN:g}")
    if tick_cell is None:
        raise SmokeError("serve: no mid-decode tick was captured")
    summary = {"requests": len(done), "waves": waves,
               "ticks": eng.stats["ticks"], "logit_err": worst,
               "logit_tol": GEMMA_LOGIT_TOL,
               "first_token_agree": f"{first_agree}/{N_REQUESTS}",
               "token_agree": f"{tok_agree}/{len(gaps)}",
               "token_gap": worst_gap, "token_margin": TOKEN_MARGIN,
               "params": n_params, **times}
    return summary, tick_cell


# ---------------------------------------------------------------------------
# phase 3: the decode tick under graph-level noise
# ---------------------------------------------------------------------------

def decode_probe(tick_cell, clock: CompileClock, *, name: str) -> dict:
    from repro.core import Controller, step_region
    from repro.core.noise import NoiseScale, make_modes

    registry = make_modes(NoiseScale(hbm_mib=32, chase_len=1 << 20))
    tk_fn, tk_args = tick_cell
    region = step_region(name, tk_fn, tk_args,
                         {m: registry[m] for m in DECODE_MODES})
    ctl = Controller(reps=PROBE_REPS)
    out: dict = {}
    with clock.phase("decode_probe", out):
        for mode in DECODE_MODES:
            r = ctl.run_mode(region, mode, ks=DECODE_KS)
            inj = r.injection
            if inj is None or not inj.ok():
                raise SmokeError(f"{name}/{mode}: payload not verified "
                                 f"({inj})")
            ts = ", ".join(f"k={k}: {t * 1e3:.3f} ms"
                           for k, t in zip(r.curve.ks, r.curve.ts))
            print(f"  {name}/{mode}: payload {inj.payload}/{inj.expected} "
                  f"(survival {inj.survival_fraction:.0%}), Abs^raw "
                  f"{r.fit.k1:.1f}; {ts}", flush=True)
            out[mode] = {"payload": f"{inj.payload}/{inj.expected}",
                         "abs_raw": r.fit.k1,
                         "t_ms": [t * 1e3 for t in r.curve.ts]}
    return out


# ---------------------------------------------------------------------------
# --chips 4: collectives on a real mesh
# ---------------------------------------------------------------------------

def _ici_numpy(mode: str, v, k: int, size: int):
    """What k chained patterns of ``mode`` leave in the noise buffer."""
    import numpy as np

    v = np.asarray(v, np.float64)
    if mode == "ici_allreduce":      # psum of a replicated buffer / size
        return v
    shards = np.split(v, size)
    if mode == "ici_allgather":      # every shard becomes shard 0
        return np.concatenate([shards[0]] * size if k else shards)
    chunk = shards[0].shape[0] // size    # all-to-all transposes chunks
    blocks = np.array([s[:size * chunk].reshape(size, chunk)
                       for s in shards])
    for _ in range(k):
        blocks = blocks.transpose(1, 0, 2)
    return np.concatenate([np.concatenate([b.reshape(-1),
                                           s[size * chunk:]])
                           for b, s in zip(blocks, shards)])


def ici_phase(clock: CompileClock, *, n_chips: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import compat
    from repro.core import Controller, step_region
    from repro.core.injector import inject
    from repro.core.noise import NoiseScale, make_modes

    d_model, d_ff, batch = ICI_MLP
    devs = jax.devices()[:n_chips]
    mesh = compat.make_mesh((1, n_chips), ("data", "model"), devices=devs)
    rng = np.random.default_rng(0)
    x = jax.device_put(rng.standard_normal((batch, d_model), np.float32),
                       NamedSharding(mesh, P()))
    w1 = jax.device_put(rng.standard_normal((d_model, d_ff), np.float32)
                        / np.sqrt(d_model), NamedSharding(mesh, P(None,
                                                                   "model")))
    w2 = jax.device_put(rng.standard_normal((d_ff, d_model), np.float32)
                        / np.sqrt(d_ff), NamedSharding(mesh, P("model",
                                                               None)))

    def tp_step(x, w1, w2):         # column- then row-parallel MLP
        return jax.nn.gelu(x @ w1) @ w2

    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(tp_step)(x, w1, w2), np.float64)
    xs, a, b = (np.asarray(t, np.float64) for t in (x, w1, w2))
    h = xs @ a
    h = 0.5 * h * (1 + np.tanh(np.sqrt(2 / np.pi) * (h + 0.044715 * h ** 3)))
    step_err = float(np.max(np.abs(got - h @ b)) / np.max(np.abs(h @ b)))
    print(f"  tensor-parallel step on a (1, {n_chips}) mesh: max normalized "
          f"error vs NumPy {step_err:.3g}", flush=True)
    if step_err > 1e-3:
        raise SmokeError(f"ici: the tensor-parallel step is off by "
                         f"{step_err:.3g}")

    registry = make_modes(NoiseScale(ici_kib=256), mesh=mesh,
                          ici_axis="model")
    region = step_region(f"tp_mlp_d{d_model}_f{d_ff}_x{n_chips}", tp_step,
                         (x, w1, w2), {m: registry[m] for m in ICI_MODES})
    ctl = Controller(reps=PROBE_REPS)
    out: dict = {"tp_step_err": step_err}
    with clock.phase("ici", out):
        for mode in ICI_MODES:
            m = registry[mode]
            state = m.make_state(jax.random.PRNGKey(0))
            k = ICI_KS[-1]
            noisy = jax.jit(inject(tp_step, m, k))
            hlo = noisy.lower(state, x, w1, w2).compile().as_text()
            n_ops = hlo.count(f" {ICI_OPCODE[mode]}(") + hlo.count(
                f" {ICI_OPCODE[mode]}-start(")
            _, _, new_state = noisy(state, x, w1, w2)
            want = _ici_numpy(mode, state["v"], k, n_chips)
            err = float(np.max(np.abs(np.asarray(new_state["v"],
                                                  np.float64) - want))
                        / max(np.max(np.abs(want)), 1e-30))
            r = ctl.run_mode(region, mode, ks=ICI_KS)
            inj = r.injection
            ok = n_ops > 0 and err < 1e-5 and inj is not None and inj.ok()
            print(f"  {mode}: {n_ops} {ICI_OPCODE[mode]} instruction(s) "
                  f"in the compiled HLO at k={k} (async start/done clones "
                  f"included); buffer vs NumPy {err:.3g}; "
                  f"payload {inj.payload if inj else None}/{k if inj else '-'}"
                  f"; Abs^raw {r.fit.k1:.1f}; t(k)="
                  + ", ".join(f"{t * 1e3:.3f} ms" for t in r.curve.ts),
                  flush=True)
            out[mode] = {"hlo_ops": n_ops, "numpy_err": err,
                         "payload": f"{inj.payload}/{inj.expected}"
                         if inj else None, "abs_raw": r.fit.k1,
                         "t_ms": [t * 1e3 for t in r.curve.ts]}
            if not ok:
                raise SmokeError(f"ici: {mode} failed (ops {n_ops}, "
                                 f"err {err:.3g}, payload {inj})")
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the ICI phase on a (1, 4) mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="where plans, stores, reports and the summary go")
    args = ap.parse_args(argv)

    refuse_unless_clean_env()
    devs = require_tpu(args.chips)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {src}; run "
                         "this script from a checkout of the repository")
    sys.path.insert(0, str(src))

    from repro.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}; compile cache: {cache_dir}", flush=True)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    clock = CompileClock()
    summary: dict = {"device": device}
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            print("== ICI phase: collectives on a (1, 4) mesh", flush=True)
            summary["ici"] = ici_phase(clock, n_chips=4)
        else:
            from repro.configs import get_config

            print("== phase 1: kernel campaign through fleet run", flush=True)
            summary["kernels"] = kernel_campaign(out_dir, clock)
            print("== phase 2: Gemma-2B serve (full width)", flush=True)
            cfg = get_config("gemma-2b")
            summary["serve"], tick = gemma_serve(cfg, clock, seed=args.seed)
            print("== phase 3: decode tick under graph-level noise",
                  flush=True)
            summary["decode_probe"] = decode_probe(
                tick, clock, name=f"{cfg.name}_serve_decode_b{N_SLOTS}")
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    summary["wall_s"] = time.perf_counter() - t0
    summary["compile_s"] = clock.total
    with open(out_dir / f"summary_chips{args.chips}.json", "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True, default=str)
    print(f"total {summary['wall_s']:.1f} s (compile {clock.total:.1f} s); "
          f"summary -> {out_dir / f'summary_chips{args.chips}.json'}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
