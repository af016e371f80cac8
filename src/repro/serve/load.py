"""Synthetic-traffic load harness for the serving engine, and the probe's
serve RegionTargets.

The harness drives a ``ServeEngine`` with a reproducible request stream —
closed-loop (keep N requests outstanding) or Poisson arrivals (exponential
inter-arrival gaps measured in engine ticks, so runs are deterministic and
machine-independent) — over prompt/decode length mixes, and reports
tokens/sec plus page-pool occupancy:

    PYTHONPATH=src python -m repro.serve.load --arch gemma-2b --mix quick \
        [--dense] [--slots 4] [--json out.json]

``build_serve_regions`` turns the same engine into the fleet's ``"serve"``
TargetSpec kind: it snapshots the engine's batched prefill and decode tick
as two pure cells (``ServeEngine.probe_cells``) and wraps each as a
graph-level-noise RegionTarget — prefill and decode classify as SEPARATE
regions of one serving workload (the paper's verdict-flip payoff: prefill
is compute-bound, decode bandwidth/latency-bound).
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import time
from typing import Optional, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class LoadSpec:
    """One reproducible traffic mix: request count, arrival process, and the
    prompt/decode length distributions (sampled with ``seed``)."""
    n_requests: int = 16
    arrival: str = "closed"          # "closed" | "poisson"
    concurrency: int = 8             # closed-loop: max outstanding requests
    mean_gap_ticks: float = 2.0      # poisson: mean inter-arrival (ticks)
    prompt_lens: tuple[int, ...] = (8, 16, 32)
    max_new: tuple[int, ...] = (4, 8, 16)
    seed: int = 0


# named mixes; prompt/decode lengths are clamped to the target config's
# max_seq by sample_requests, so one mix spans the whole configs/ zoo
MIXES: dict[str, LoadSpec] = {
    "quick": LoadSpec(n_requests=8, prompt_lens=(4, 8, 12),
                      max_new=(4, 6, 8), concurrency=8),
    "chat": LoadSpec(n_requests=24, prompt_lens=(16, 32, 64),
                     max_new=(8, 16, 32), concurrency=8),
    "long": LoadSpec(n_requests=12, prompt_lens=(64, 128, 256),
                     max_new=(32, 64), concurrency=4),
    "poisson": LoadSpec(n_requests=16, arrival="poisson",
                        mean_gap_ticks=3.0, prompt_lens=(8, 16, 32),
                        max_new=(4, 8, 16)),
}


def sample_requests(spec: LoadSpec, vocab_size: int, max_seq: int
                    ) -> list[dict]:
    """The mix's deterministic request stream: ``[{prompt, max_new,
    arrival_tick}, ...]`` sorted by arrival. Lengths clamp to the config's
    ``max_seq`` so a mix written for 4k contexts still drives a smoke
    config."""
    if spec.arrival not in ("closed", "poisson"):
        raise ValueError(f"arrival {spec.arrival!r}: one of "
                         "['closed', 'poisson']")
    rng = np.random.default_rng(spec.seed)
    reqs = []
    tick = 0.0
    for _ in range(spec.n_requests):
        plen = int(min(rng.choice(spec.prompt_lens), max_seq - 1))
        new = int(rng.choice(spec.max_new))
        if spec.arrival == "poisson":
            tick += float(rng.exponential(spec.mean_gap_ticks))
        reqs.append({
            "prompt": rng.integers(1, vocab_size, size=plen).tolist(),
            "max_new": new,
            "arrival_tick": int(tick),
        })
    return reqs


def run_load(engine, spec: LoadSpec, *, max_ticks: int = 10000) -> dict:
    """Drive ``engine`` with the mix and report throughput/occupancy.

    Closed-loop keeps at most ``spec.concurrency`` requests outstanding;
    Poisson releases requests by their arrival tick. Returns the engine's
    ``report()`` extended with per-request latency (in ticks) percentiles.
    """
    stream = sample_requests(spec, engine.cfg.vocab_size, engine.max_seq)
    pending = list(stream)
    born: dict[int, int] = {}
    latency: list[int] = []
    tracked = []
    t0 = time.perf_counter()
    tick = 0
    while (pending or engine.queue
           or any(r is not None for r in engine.slot_req)):
        if tick >= max_ticks:
            break
        while pending and _admissible(spec, pending[0], engine, tick):
            item = pending.pop(0)
            req = engine.submit(item["prompt"], max_new=item["max_new"])
            born[req.uid] = tick
            tracked.append(req)
        engine.step()
        tick += 1
        for r in tracked:
            if r.done and r.uid in born:
                latency.append(tick - born.pop(r.uid))
    wall = time.perf_counter() - t0
    engine.stats["wall_s"] += wall
    rep = engine.report()
    rep.update({
        "mix": dataclasses.asdict(spec),
        "requests_done": sum(r.done for r in tracked),
        "requests_total": len(stream),
        "latency_ticks_p50": float(np.percentile(latency, 50))
        if latency else None,
        "latency_ticks_p95": float(np.percentile(latency, 95))
        if latency else None,
    })
    return rep


def _admissible(spec: LoadSpec, item: dict, engine, tick: int) -> bool:
    if spec.arrival == "poisson":
        return item["arrival_tick"] <= tick
    outstanding = len(engine.queue) + sum(
        r is not None for r in engine.slot_req)
    return outstanding < spec.concurrency


# ---------------------------------------------------------------------------
# Probe integration: the "serve" TargetSpec kind's region builder
# ---------------------------------------------------------------------------

# a serve target's params: the smoke engine (no ``layers``) or a published
# configuration cut to ``layers`` layers, its weights drawn from ``seed`` or
# read from ``weights``, a directory of one .npy a parameter
# (``load_weights``)
SMOKE_PARAMS = frozenset({"arch", "slots", "prompt", "max_new", "page_size"})
PUBLISHED_PARAMS = frozenset({"arch", "layers", "slots", "max_seq",
                              "page_size", "prompt_lens", "max_new",
                              "regions", "seed", "weights"})
SERVE_REGIONS = ("prefill", "decode")
WARM_TICKS = 2          # ticks between admission and the probed state

# the engine a published serve target built in this process, by its params:
# a plan resolves once per plan object, and a campaign holds several (the
# fleet's audit and classify, each in-process worker), which must share one
# engine — two do not fit beside each other on a chip, so it holds at most
# one
_ENGINES: dict = {}


def check_serve_params(params: dict) -> None:
    """Reject what a serve target cannot build: unknown params, a missing
    or unknown arch, a bad count, ``layers`` outside 1..n_layers, a prompt
    that leaves no room for its ``max_new`` tokens within ``max_seq``,
    regions other than prefill and decode, and a ``weights`` that is not a
    path. Raises ValueError."""
    from repro.configs import get_config

    allowed = PUBLISHED_PARAMS if "layers" in params else SMOKE_PARAMS
    unknown = sorted(set(params) - allowed)
    if unknown:
        raise ValueError(f"unknown serve param(s) {unknown}; "
                         f"{'with' if 'layers' in params else 'without'} "
                         f"'layers' one of {sorted(allowed)}")
    if not params.get("arch"):
        raise ValueError("serve target needs an 'arch'")
    try:
        cfg = get_config(params["arch"])
    except KeyError as e:
        raise ValueError(str(e)) from None

    for key in ("slots", "prompt", "max_new", "page_size", "max_seq",
                "layers"):
        v = params.get(key)
        if v is not None and not _positive_int(v):
            raise ValueError(f"serve target {key}={v!r}: want a positive "
                             "int")
    if "layers" not in params:
        return
    p = _published(params)
    if p["layers"] > cfg.n_layers:
        raise ValueError(f"serve target layers={p['layers']}: "
                         f"{cfg.name} has {cfg.n_layers}")
    if p["max_seq"] % p["page_size"]:
        raise ValueError(f"serve target max_seq={p['max_seq']} is not a "
                         f"multiple of page_size={p['page_size']}")
    lens = p["prompt_lens"]
    if (not isinstance(lens, (list, tuple)) or not lens
            or len(lens) > p["slots"]
            or not all(_positive_int(n) for n in lens)):
        raise ValueError(f"serve target prompt_lens={lens!r}: want 1 to "
                         f"slots={p['slots']} positive ints (one wave)")
    if max(lens) + p["max_new"] > p["max_seq"]:
        raise ValueError(f"serve target: a {max(lens)}-token prompt and "
                         f"max_new={p['max_new']} exceed "
                         f"max_seq={p['max_seq']}")
    if p["max_new"] <= WARM_TICKS:
        raise ValueError(f"serve target max_new={p['max_new']}: the "
                         f"{WARM_TICKS} warm ticks and the probed tick need "
                         "more")
    regions = p["regions"]
    if (not isinstance(regions, (list, tuple)) or not regions
            or len(set(regions)) != len(regions)
            or not set(regions) <= set(SERVE_REGIONS)):
        raise ValueError(f"serve target regions={regions!r}: want distinct "
                         f"names from {list(SERVE_REGIONS)}")
    seed = p["seed"]
    if isinstance(seed, bool) or not isinstance(seed, int) \
            or not 0 <= seed < 1 << 32:
        raise ValueError(f"serve target seed={seed!r}: want an int in "
                         "[0, 2**32)")
    weights = p.get("weights")
    if weights is not None and (not isinstance(weights, str) or not weights):
        raise ValueError(f"serve target weights={weights!r}: want the path "
                         "of a directory")


def _positive_int(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool) and v >= 1


def _published(params: dict) -> dict:
    """A published serve target's params with their defaults filled in."""
    return {"slots": 4, "max_seq": 4096, "page_size": 16,
            "prompt_lens": [32], "max_new": 8, "regions": list(SERVE_REGIONS),
            "seed": 0, **params}


def _tag(p: dict) -> str:
    """Every engine param of a published target, for its region names:
    campaigns that differ in any of them must not share store records."""
    lens = [int(n) for n in p["prompt_lens"]]
    shown = "-".join(map(str, lens)) if len(lens) <= 4 else \
        "h" + hashlib.sha1(json.dumps(lens).encode()).hexdigest()[:8]
    tag = (f"b{p['slots']}_m{p['max_seq']}_p{p['page_size']}_s{shown}"
           f"_n{p['max_new']}_r{p['seed']}")
    if p.get("weights"):
        tag += "_w" + hashlib.sha1(p["weights"].encode()).hexdigest()[:8]
    return tag


def serve_target_names(params: dict) -> list[str]:
    """The region names a serve target's params resolve to, WITHOUT
    building a model (plan grid queries must stay cheap)."""
    if "layers" not in params:
        return serve_region_names(
            params["arch"], slots=int(params.get("slots", 4)),
            prompt=int(params.get("prompt", 32)),
            max_new=int(params.get("max_new", 8)),
            page_size=int(params.get("page_size", 16)))
    from repro.configs import get_config

    p = _published(params)
    base = f"{get_config(p['arch']).name}_L{p['layers']}_serve"
    return [f"{base}_{r}_{_tag(p)}" for r in p["regions"]]


def serve_region_names(arch: str, *, slots: int = 4, prompt: int = 32,
                       max_new: int = 8, page_size: int = 16) -> list[str]:
    """The smoke engine's region names, WITHOUT building a model. Every
    engine parameter the smoke engine varies over is encoded — campaigns
    differing only in ``max_new`` or ``page_size`` must NOT collide in the
    store."""
    from repro.configs import get_smoke_config
    base = f"{get_smoke_config(arch).name}_serve"
    tag = f"s{prompt}_n{max_new}_p{page_size}_b{slots}"
    return [f"{base}_prefill_{tag}", f"{base}_decode_{tag}"]


def _build_engine_for_probe(arch: str, *, slots: int, prompt: int,
                            max_new: int, page_size: int):
    """A paged smoke engine two ticks into a full-slot campaign — the state
    ``ServeEngine.probe_cells`` snapshots for the serve RegionTargets."""
    import jax

    from repro.configs import get_smoke_config
    from repro.models.model import build
    from repro.serve.engine import ServeEngine

    cfg = get_smoke_config(arch)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    need = prompt + max_new + 2
    max_seq = page_size
    while max_seq < need:
        max_seq *= 2
    eng = ServeEngine(api, params, n_slots=slots, max_seq=max_seq,
                      paged=True, page_size=page_size)
    rng = np.random.default_rng(0)
    for _ in range(slots):
        eng.submit(rng.integers(1, cfg.vocab_size, size=prompt).tolist(),
                   max_new=max_new)
    eng.step()       # admission wave (the prefill cell's state) + tick 1
    eng.step()       # tick 2: a representative mid-decode state
    return eng


def probed_engine(params: dict):
    """The paged engine of a published serve target (params with
    ``layers``), built once in this process until ``release_serve_engines``:
    ``get_config(arch)`` with ``n_layers`` cut to ``layers``, weights read
    from ``weights`` or else drawn from ``seed``, one admission wave of
    prompts whose token ids are drawn from ``seed``, then ``WARM_TICKS``
    ticks — the state
    ``ServeEngine.probe_cells`` snapshots. The process holds one such
    engine: building another frees the one before."""
    p = _published(params)
    key = json.dumps(p, sort_keys=True)
    if key not in _ENGINES:
        release_serve_engines()
        _ENGINES[key] = _build_published_engine(p)
    return _ENGINES[key]


def _build_published_engine(p: dict):
    import jax

    from repro.configs import get_config
    from repro.models.model import build
    from repro.serve.engine import ServeEngine
    from repro.spans import span

    with span("campaign.serve.build", layers=p["layers"],
              slots=p["slots"]):
        cfg = get_config(p["arch"]).scaled(n_layers=p["layers"])
        api = build(cfg)
        with span("campaign.serve.build.weights"):
            if p.get("weights"):
                params = load_weights(p["weights"], jax.eval_shape(
                    api.init, jax.random.PRNGKey(0)))
            else:
                params = jax.jit(api.init)(jax.random.PRNGKey(p["seed"]))
            jax.block_until_ready(params)
        eng = ServeEngine(api, params, n_slots=p["slots"],
                          max_seq=p["max_seq"], paged=True,
                          page_size=p["page_size"])
        rng = np.random.default_rng(p["seed"])
        for n in p["prompt_lens"]:
            eng.submit(rng.integers(1, cfg.vocab_size, size=n).tolist(),
                       max_new=p["max_new"])
        with span("campaign.serve.build.admit"):
            eng.admit()
        with span("campaign.serve.build.warm", ticks=WARM_TICKS):
            for _ in range(WARM_TICKS):
                eng.step()
    return eng


def load_weights(directory: str, like):
    """The weights in ``directory`` (one .npy a leaf,
    ``repro.ckpt.checkpoint.read_leaves``) as device arrays shaped like
    ``like``. On a v5e host the 9.4 GB of DeepSeek-Coder-33B's 8 layers
    took 10–12 s to reach the chip this way, where drawing them on the
    device from ``seed`` takes about 1 s (PERF.md)."""
    import jax

    from repro.ckpt import checkpoint

    return jax.device_put(checkpoint.read_leaves(directory, like))


def release_serve_engines() -> int:
    """Free every engine ``probed_engine`` built in this process: its
    weights, pages and state leave the device at once, even where a region
    that wraps them is still referenced. Returns how many were freed."""
    import jax

    n = len(_ENGINES)
    for eng in _ENGINES.values():
        for x in jax.tree.leaves((eng.params, eng.cache, eng.pos, eng.cur,
                                  eng.page_table, eng._active_dev,
                                  eng._last_wave)):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()
    _ENGINES.clear()
    return n


def build_serve_regions(arch: str, modes: Sequence[str], *, slots: int = 4,
                        prompt: int = 32, max_new: int = 8,
                        page_size: int = 16) -> list:
    """Build the smoke serve workload's two RegionTargets: the paged
    engine's batched prefill and its decode tick, each snapshotted
    mid-campaign (``ServeEngine.probe_cells``) and wrapped with the
    graph-level noise registry — the same adapter
    (``core.injector.step_region``) the "step" kind uses, so both ride the
    compile-once runtime-k sweep path."""
    from repro.core import step_region

    reg = _registry(modes)
    eng = _build_engine_for_probe(arch, slots=slots, prompt=prompt,
                                  max_new=max_new, page_size=page_size)
    pf_fn, pf_args, tk_fn, tk_args = eng.probe_cells()
    pf_name, tk_name = serve_region_names(arch, slots=slots, prompt=prompt,
                                          max_new=max_new,
                                          page_size=page_size)
    return [step_region(pf_name, pf_fn, pf_args, reg),
            step_region(tk_name, tk_fn, tk_args, reg)]


def build_serve_target(params: dict, modes: Sequence[str]) -> list:
    """The RegionTargets of a serve target's params: the smoke engine's two
    without ``layers``; with it, the requested regions of the published
    configuration's engine (``probed_engine``)."""
    if "layers" not in params:
        return build_serve_regions(
            params["arch"], list(modes), slots=int(params.get("slots", 4)),
            prompt=int(params.get("prompt", 32)),
            max_new=int(params.get("max_new", 8)),
            page_size=int(params.get("page_size", 16)))
    from repro.core import step_region

    reg = _registry(modes)
    pf_fn, pf_args, tk_fn, tk_args = probed_engine(params).probe_cells()
    cells = {"prefill": (pf_fn, pf_args), "decode": (tk_fn, tk_args)}
    names = serve_target_names(params)
    return [step_region(name, *cells[r], reg)
            for name, r in zip(names, _published(params)["regions"])]


def _registry(modes: Sequence[str]) -> dict:
    from repro.core.noise import NoiseScale, make_modes

    registry = make_modes(NoiseScale(hbm_mib=32, chase_len=1 << 20))
    unknown = [m for m in modes if m not in registry]
    if unknown:
        raise SystemExit(f"unknown mode(s) {unknown}; available: "
                         f"{', '.join(sorted(registry))}")
    return {m: registry[m] for m in modes}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve.load",
        description="synthetic-traffic load harness for the serving engine")
    ap.add_argument("--arch", required=True, help="model architecture")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced smoke config (default: full config)")
    ap.add_argument("--mix", default="quick", choices=sorted(MIXES),
                    help="named traffic mix")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--dense", action="store_true",
                    help="force the dense (non-paged) cache layout")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the report as JSON")
    return ap


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.models.model import build
    from repro.serve.engine import ServeEngine

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, params, n_slots=args.slots, max_seq=args.max_seq,
                      page_size=args.page_size,
                      paged=False if args.dense else None, seed=args.seed)
    spec = dataclasses.replace(MIXES[args.mix], seed=args.seed)
    rep = run_load(eng, spec)
    print(f"== serve load: {cfg.name} mix={args.mix} "
          f"({'paged' if eng.paged else 'dense'}, slots={args.slots})")
    print(f"  {rep['requests_done']}/{rep['requests_total']} requests, "
          f"{rep['decode_tokens']} decode + {rep['prefill_tokens']} prefill "
          f"tokens in {rep['wall_s']:.2f}s")
    print(f"  decode {rep['decode_tok_s']:.1f} tok/s, total "
          f"{rep['total_tok_s']:.1f} tok/s, mean pool occupancy "
          f"{rep['mean_pool_occupancy']:.2f}")
    if rep["latency_ticks_p50"] is not None:
        print(f"  latency p50={rep['latency_ticks_p50']:.0f} "
              f"p95={rep['latency_ticks_p95']:.0f} ticks")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=1, sort_keys=True)
        print(f"  report -> {args.json}")


if __name__ == "__main__":
    main()
