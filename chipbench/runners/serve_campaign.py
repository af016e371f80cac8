"""Serve-campaign runner: a closed loop of whole noise-injection campaigns
on a model's paged decode tick, each ``repro.fleet.executor.run_fleet`` in
this process on a plan of its own with a fresh store, from the plan to a
classified report. The plan's one target is the fleet's ``serve`` kind at
the configuration's published widths, cut to its ``n_layers`` layers, with
the engine's page geometry from the configuration's ``assumed`` and the
prompts, tokens to generate and regions from the traffic mix. Weights and
prompt token ids come from the run's seed; every campaign builds its
engine anew, drawing its weights on the device, and frees it when it
ends.

Set-up builds the target once and stops at once, with an error, unless the
decode region holds the configuration's tick weights byte for byte (a
program that builds another model fails in seconds). It then runs the
payload check once per mode and, where that missed the persistent cache,
one whole campaign. The window is the campaign runner's.

``check`` holds each campaign to the plain campaign reference, as the
campaign runner does, and the tick itself to a float32 forward
(``chipbench.reference.decoder``) that never reads what the program built:
the benchmark draws weights of its own from the seed
(``chipbench.weights``) into the work dir, builds the target from them
(its ``weights`` param), calls the decode region's runtime-k build at the
largest k each mode swept on the probed state, and compares its logits
with the reference's, from the same files, over each slot's prompt and
the tokens the engine generated for it. The window's campaigns draw their
weights on the device instead: moving 9.4 GB from the host to the chip
took 10–12 s, most of a campaign.
"""
from __future__ import annotations

import gc
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts_decoder as counts
from chipbench import traffic as T
from chipbench import weights as W
from chipbench.reference import decoder as ref
from chipbench.runners.campaign import CHECK_K_UNSWEPT, CampaignCell


class SizeCheckError(RuntimeError):
    """The program built another model than the configuration's."""


def tick_weight_bytes(params: dict) -> int:
    """Bytes of the parameters a tick reads whole: all but the embedding
    table."""
    table = params["embed"]["table"]
    return sum(x.nbytes for x in jax.tree.leaves(params)) - table.nbytes


class ServeCampaignCell(CampaignCell):
    def __init__(self, ctx):
        self.tick_counts: dict = {}
        super().__init__(ctx)

    def weights_dir(self):
        """The weight files of the current seed, drawn on first use; those
        of any other seed are deleted first."""
        d = self.work / f"weights-{self.ctx.seed}"
        if not d.is_dir():
            self.drop_weights()
            W.draw(self.cfg, T.key_for(self.ctx.seed, "weights"), d)
        return d

    def drop_weights(self) -> None:
        for d in self.work.glob("weights-*"):
            shutil.rmtree(d)

    def target_params(self, *, weights: bool = False) -> dict:
        """The serve target's params; with ``weights``, built from the
        benchmark's weight files."""
        c, mix = self.cfg, self.mix
        params = {"arch": c["arch"], "layers": c["n_layers"],
                  **c["assumed"], "prompt_lens": list(mix["prompt_lens"]),
                  "max_new": mix["max_new"], "regions": list(mix["regions"]),
                  "seed": T.key_for(self.ctx.seed, "serve")}
        if weights:
            params["weights"] = str(self.weights_dir())
        return params

    def _plan(self, name: str, *, weights: bool = False):
        from repro.fleet.plan import SweepPlan, TargetSpec

        plan = SweepPlan(name=name, store=str(self.work / f"{name}.jsonl"),
                         targets=[TargetSpec(
                             "serve", tuple(self.mix["modes"]),
                             self.target_params(weights=weights))],
                         reps=self.mix["reps"], shards=1,
                         backend=self.backend)
        plan.save(str(self.work / f"{name}.plan.json"))
        return plan

    @staticmethod
    def decode_region(plan):
        """The decode tick's region of ``plan``'s one serve target."""
        (_spec, regions), = plan.resolve()
        found = [r for r in regions if "_decode_" in r.name]
        if len(found) != 1:
            raise SizeCheckError(f"the serve target resolved to "
                                 f"{[r.name for r in regions]}, want one "
                                 "decode region")
        return found[0]

    def size_check(self, region) -> int:
        """The decode region's tick weights, in bytes, against the
        configuration's count; raises where they differ."""
        want = counts.tick_weight_params(self.cfg) \
            * counts.param_bytes(self.cfg)
        try:
            params = region.args_for_rt(self.mix["modes"][0])[1]
            got = tick_weight_bytes(params)
        except Exception as e:      # noqa: BLE001 - any other shape fails
            raise SizeCheckError(f"size check: cannot read the decode "
                                 f"region's weights: {e!r}") from e
        if got != want:
            raise SizeCheckError(
                f"size check: the decode region {region.name!r} holds "
                f"{got:,} bytes of tick weights; the configuration "
                f"{self.cfg['name']!r} has {want:,}")
        return got

    def count_tick(self) -> int:
        """The probed tick's byte and operation counts, from the positions
        the probed engine holds: each active slot attends to its position
        and every one before it. Returns the live positions."""
        from repro.serve.load import probed_engine

        eng = probed_engine(self.target_params())
        pos = np.asarray(eng.pos)[eng.active]
        live = int(np.sum(pos + 1))
        slots = self.cfg["assumed"]["slots"]
        self.tick_counts = {
            "tick_bytes": counts.tick_bytes(self.cfg, slots, live),
            "tick_flops": counts.tick_flops(self.cfg, slots, live)}
        return live

    def _warm_up(self) -> dict:
        """Build the target once and check its size; run the payload check
        once per mode, and where it missed the persistent cache one whole
        campaign (see the campaign runner)."""
        c0 = self.clock.reading()
        t0 = time.perf_counter()
        plan = self._plan("warm")
        try:
            region = self.decode_region(plan)
            size = self.size_check(region)
            live = self.count_tick()
            for mode in self.mix["modes"]:
                region.payload_check(mode, 1)
        finally:
            release(plan)
        del plan
        gc.collect()
        misses = self.clock.misses - c0[2]
        if misses:
            self._campaign()
            self.campaigns.clear()
        c1 = self.clock.reading()
        return {"s": time.perf_counter() - t0, "compile_s": c1[0] - c0[0],
                "executables": c1[1] - c0[1], "cache_misses": c1[2] - c0[2],
                "campaign": bool(misses), "tick_weight_bytes": size,
                "live_positions": live}

    def _k0_calls(self) -> None:
        """Inside the trace, after the traced campaign: the decode region's
        runtime-k build at k=0 (``bench.tick_k0``) and the engine's plain
        tick (``bench.plain_tick``), ``probe_calls`` times each, on an
        engine built anew with the campaign's params."""
        n = int(self.mix.get("probe_calls", 5))
        tracer = self.ctx.tracer
        plan = self._plan("k0")
        try:
            with tracer.span("bench.tick_build"):
                region = self.decode_region(plan)
            mode = self.mix["modes"][0]
            calls = {"bench.tick_k0": (region.build_rt(mode),
                                       (jnp.int32(0),
                                        *region.args_for_rt(mode))),
                     "bench.plain_tick": (region.build("", 0),
                                          region.args_for("", 0))}
            for name, (fn, args) in calls.items():
                jax.block_until_ready(fn(*args))
                with tracer.span(name):
                    for _ in range(n):
                        jax.block_until_ready(fn(*args))
        finally:
            release(plan)

    def counters(self) -> dict:
        return {"campaigns": [c for c in self.campaigns
                              if "failed" not in c], **self.tick_counts}

    def free_program(self) -> None:
        from repro.serve.load import release_serve_engines

        release_serve_engines()
        gc.collect()

    def tick_logits(self, region, mode: str) -> np.ndarray:
        """The decode region's runtime-k build at the largest k ``mode``
        swept: its logits, (slots, vocab)."""
        k = self.swept_k.get(mode, CHECK_K_UNSWEPT)
        out = region.build_rt(mode)(jnp.int32(k), *region.args_for_rt(mode))
        return np.asarray(out[0][4], np.float64)

    def reference_logits(self, engine) -> np.ndarray:
        """The float32 forward's logits, from the weight files, after each
        slot's prompt and the tokens the engine generated for it."""
        c = self.cfg
        seqs = [r.prompt + r.out for r in engine.slot_req]
        return ref.last_logits(W.by_role(self.weights_dir(), c), seqs,
                               norm_eps=c["norm_eps"],
                               rope_theta=c["rope_theta"],
                               rope_factor=c["rope_scaling"]["factor"])

    def tick_error(self) -> float:
        """max|logits - ref| / max|ref| over the slots and the modes, of the
        target built from the benchmark's weight files."""
        from repro.serve.load import probed_engine

        plan = self._plan("check", weights=True)
        try:
            region = self.decode_region(plan)
            engine = probed_engine(self.target_params(weights=True))
            got = {m: self.tick_logits(region, m) for m in self.mix["modes"]}
            want = self.reference_logits(engine)
            return max(ref.max_rel_err(g, want) for g in got.values())
        finally:
            release(plan)

    def check(self) -> list:
        lim = self.mix["correct"]
        try:
            with self.ctx.tracer.span("bench.reference"):
                found = self.campaign_mismatches()
                found["tick_logits_max_rel_err"] = self.tick_error()
        finally:
            self.drop_weights()
        return [(name, found[name], lim[name]) for name in lim]


def release(plan) -> None:
    """Free what the plan's targets hold on the device, where the program
    can (a program without ``SweepPlan.release`` frees at exit)."""
    if hasattr(plan, "release"):
        plan.release()


Cell = ServeCampaignCell
