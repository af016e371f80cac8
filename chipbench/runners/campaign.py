"""Campaign runner: a closed loop of whole noise-injection campaigns, each
``repro.fleet.executor.run_fleet`` in this process on a plan of its own
with a fresh store, from the plan to a classified report.

Set-up runs the payload check once per mode (the oracles' eager
operations and the reference), and in a fresh cache one whole campaign,
so that the window compiles nothing (``_warm_up``). The window then runs
campaigns back to back and closes at the first campaign boundary after
``seconds``.

``check`` holds each campaign to a plain reference rebuilt from the raw
records of its store (``chipbench.reference.campaign``): the k grid, the
payload check of every (region, mode) pair, the fits and the verdicts. It
also calls the runtime-k build of each swept region, at the largest k each
mode swept, on inputs the benchmark made from the seed at the timed size,
and holds its main output to a NumPy float64 product.
"""
from __future__ import annotations

import contextlib
import io
import shutil
import time

import jax
import numpy as np

from chipbench import traffic as T
from chipbench.reference import campaign as cref
from chipbench.reference import spmxv as ref

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
# the k the check runs at where no campaign finished to say what it swept
CHECK_K_UNSWEPT = 16


class CompileClock:
    """Seconds JAX spends lowering and compiling, the executables it
    compiled or read from the persistent cache (JAX times both as a backend
    compile), and the persistent cache's misses, from its monitoring
    events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_MISS:
            self.misses += 1

    def reading(self) -> tuple:
        return self.seconds, self.compiles, self.misses


class CampaignCell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.mix = ctx.config, ctx.traffic
        self.backend = self.mix["backend"]
        self.work = ctx.work_dir / "campaign"
        shutil.rmtree(self.work, ignore_errors=True)
        self.work.mkdir(parents=True)
        self.clock = CompileClock()
        self.campaigns: list[dict] = []
        self.swept_k: dict = {}
        self._n = 0
        self.warm_up = self._warm_up()

    def _plan(self, name: str):
        from repro.fleet.plan import SweepPlan, TargetSpec

        c = self.cfg
        params = {"kernel": c["kernel"], "sizes": [c["rows"]],
                  "qs": list(c["qs"]), "nnz_per_row": c["nnz_per_row"],
                  "br": c["block_rows"],
                  "seed": T.key_for(self.ctx.seed, f"plan-{name}")}
        plan = SweepPlan(name=name, store=str(self.work / f"{name}.jsonl"),
                         targets=[TargetSpec("pallas", tuple(self.mix["modes"]),
                                             params)],
                         reps=self.mix["reps"], shards=1,
                         backend=self.backend)
        plan.save(str(self.work / f"{name}.plan.json"))
        return plan

    def _warm_up(self) -> dict:
        """Run once what every campaign runs alike: the payload check, with
        its oracles' eager operations and the float64 reference. A Mosaic
        kernel's persistent-cache key carries the Python call stack it was
        traced from, so only a campaign compiles the kernels that the
        window's campaigns look up: where the check had to compile (a
        fresh cache), set-up also runs one whole campaign."""
        c0 = self.clock.reading()
        t0 = time.perf_counter()
        plan = self._plan("warm")
        for _spec, regions in plan.resolve():
            for mode in self.mix["modes"]:
                regions[0].payload_check(mode, 1)
        misses = self.clock.misses - c0[2]
        if misses:
            self._campaign()
            self.campaigns.clear()
        c1 = self.clock.reading()
        return {"s": time.perf_counter() - t0, "compile_s": c1[0] - c0[0],
                "executables": c1[1] - c0[1], "cache_misses": c1[2] - c0[2],
                "campaign": bool(misses)}

    def _campaign(self) -> None:
        """One campaign; a campaign that raises is recorded as failed (the
        program's own payload check raises on a wrong kernel output)."""
        from repro.fleet.executor import run_fleet
        from repro.fleet.launchers import LocalLauncher

        self._n += 1
        plan = self._plan(f"c{self._n}")
        c0 = self.clock.reading()
        t0 = time.perf_counter()
        log = io.StringIO()
        try:
            with self.ctx.tracer.span("campaign.run_fleet"), \
                    contextlib.redirect_stdout(log):
                res = run_fleet(str(self.work / f"{plan.name}.plan.json"),
                                fresh=True,
                                launcher=LocalLauncher(in_process=True))
        except Exception as e:      # noqa: BLE001 - counted, run goes on
            print(f"campaign failed: {type(e).__name__}: {e}", flush=True)
            self.campaigns.append({"failed": f"{type(e).__name__}: {e}"[:300],
                                   "wall_s": time.perf_counter() - t0})
            return
        wall = time.perf_counter() - t0
        c1 = self.clock.reading()
        report, points = {}, 0
        for name, rep in res.reports.items():
            modes = {}
            for mode, r in rep.results.items():
                points += len(r.curve.ks)
                self.swept_k[mode] = max(self.swept_k.get(mode, 0),
                                         max(r.curve.ks))
                modes[mode] = {"ks": list(r.curve.ks), "k1": r.fit.k1,
                               "k1_threshold": r.fit.k1_threshold,
                               "k2": r.fit.k2}
            report[name] = {"label": rep.bottleneck.label, "modes": modes}
        self.campaigns.append({
            "wall_s": wall, "compile_s": c1[0] - c0[0],
            "executables": c1[1] - c0[1], "cache_misses": c1[2] - c0[2],
            "points": points, "traced": self.ctx.tracer.recording,
            "store": plan.store, "pairs": plan.grid(), "report": report})

    def window(self, seconds: float) -> dict:
        tracer = self.ctx.tracer
        n_traced = int(self.mix.get("trace_campaigns", 1))
        t0 = time.perf_counter()
        tracer.start()
        while True:
            if len(self.campaigns) == n_traced and tracer.recording:
                self._k0_calls()
                tracer.stop()
            self._campaign()
            if time.perf_counter() - t0 >= seconds:
                break
        if tracer.recording:
            self._k0_calls()
            tracer.stop()
        wall = time.perf_counter() - t0
        ok = [c for c in self.campaigns if "failed" not in c]
        brief = [{k: v for k, v in c.items()
                  if k not in ("store", "pairs", "report")}
                 | ({"verdicts": {r: v["label"]
                                  for r, v in sorted(c["report"].items())}}
                    if "report" in c else {})
                 for c in self.campaigns[:8]]
        return {"attempted": len(self.campaigns),
                "failed": len(self.campaigns) - len(ok),
                # a failed campaign's time stays in the window; only whole
                # campaigns that reached a verdict count
                "metrics": {"campaign_s": wall / max(len(ok), 1)},
                "notes": {"warm_up": self.warm_up, "campaigns": brief,
                          "window_s": wall}}

    def _k0_calls(self, n: int = 5) -> None:
        """Inside the trace, a few calls of the runtime-k build at k=0,
        which the kernel's roofline share reads."""
        region = self._region(self.cfg["qs"][0])
        fn = region.build_rt(self.mix["modes"][0])
        args = region.args_for_rt(self.mix["modes"][0])
        jax.block_until_ready(fn(np.int32(0), *args))
        with self.ctx.tracer.span("bench.kernel_k0"):
            for _ in range(n):
                jax.block_until_ready(fn(np.int32(0), *args))

    def _region(self, q: float):
        from repro.kernels.region import pallas_region

        c = self.cfg
        return pallas_region(c["kernel"], backend=self.backend, n=c["rows"],
                             nnz_per_row=c["nnz_per_row"], q=float(q),
                             br=c["block_rows"])

    def counters(self) -> dict:
        return {"campaigns": [c for c in self.campaigns
                              if "failed" not in c],
                "rows": self.cfg["rows"],
                "nnz_per_row": self.cfg["nnz_per_row"]}

    def free_program(self) -> None:
        pass

    def main_outputs(self, q: float, mode: str, inputs) -> np.ndarray:
        """The runtime-k build's main output at the largest k ``mode``
        swept, on ``inputs`` (vals, cols, x)."""
        region = self._region(q)
        fn = region.build_rt(mode)
        k = self.swept_k.get(mode, CHECK_K_UNSWEPT)
        out = fn(np.int32(k), *(jax.numpy.asarray(a) for a in inputs))
        return np.asarray(out[0])

    def kernel_error(self) -> float:
        """The worst max|y - ref| / max|ref| over every q and mode."""
        c = self.cfg
        worst = 0.0
        for q in c["qs"]:
            inputs = ref.band_ell(c["rows"], c["nnz_per_row"], q,
                                  T.rng_for(self.ctx.seed, f"check{q}"))
            want = ref.spmv(*inputs)
            for mode in self.mix["modes"]:
                got = self.main_outputs(q, mode, inputs)
                worst = max(worst, ref.max_rel_err(got, want))
        return worst

    def campaign_mismatches(self) -> dict:
        """Failed campaigns, and the reference's mismatch counts summed
        over the campaigns that reached a verdict."""
        out = dict.fromkeys(cref.MISMATCHES, 0)
        out["campaigns_failed"] = sum("failed" in c for c in self.campaigns)
        for c in self.campaigns:
            if "failed" in c:
                continue
            got = cref.compare(cref.read_records(c["store"]), c["report"],
                               c["pairs"])
            for name, n in got.items():
                out[name] += n
        return out

    def check(self) -> list:
        lim = self.mix["correct"]
        with self.ctx.tracer.span("bench.reference"):
            found = self.campaign_mismatches()
            found["spmxv_max_rel_err"] = self.kernel_error()
        return [(name, found[name], lim[name]) for name in lim]


Cell = CampaignCell
