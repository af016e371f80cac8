"""Graph-level noise injection — wrap a whole jitted step (train/serve) with
k patterns of a noise mode.

This is the coarse-grained injection site: noise and step co-exist in one XLA
program, competing for the same chip resources under XLA's static schedule,
which plays the part of the CPU's out-of-order window in absorbing the
noise. The noise state is threaded through the wrapped step so buffers are
allocated once and patterns chain across calls; the scalar aux output is the
``volatile`` analogue (DCE-proof).

Semantics preservation is by construction: noise reads/writes only its own
state (R_n ∩ R_s = ∅) and the original outputs are returned untouched —
tests assert bit-identical outputs for every k.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import payload as payload_mod
from repro.core.noise import NoiseMode


def inject(step_fn: Callable, mode: NoiseMode, k: int) -> Callable:
    """Return ``noisy(noise_state, *args, **kw) -> (out, aux, new_state)``.

    ``out`` is bit-identical to ``step_fn(*args, **kw)``; ``aux`` is the
    DCE-proof noise scalar; ``new_state`` feeds the next call so noise
    chains persist across steps.
    """
    def noisy(noise_state, *args, **kw):
        out = step_fn(*args, **kw)
        aux, new_state = mode.apply(noise_state, k)
        # barrier: the noise must not be sunk after the step's outputs are
        # ready nor hoisted before its inputs — keep them in one schedule.
        out, aux = jax.lax.optimization_barrier((out, aux))
        return out, aux, new_state

    return noisy


def inject_rt(step_fn: Callable, mode: NoiseMode) -> Callable:
    """Runtime-k variant of ``inject``: the noise quantity is a runtime
    operand, so ONE jitted executable serves the whole k-sweep.

    Returns ``noisy(k, noise_state, *args, **kw) -> (out, aux, new_state)``
    where ``k`` is an int32 scalar (traced under jit). k leads so region
    adapters share one calling convention: ``build_rt(mode)(k, *args_rt)``.
    """
    def noisy(k, noise_state, *args, **kw):
        out = step_fn(*args, **kw)
        aux, new_state = mode.apply_rt(noise_state, k)
        out, aux = jax.lax.optimization_barrier((out, aux))
        return out, aux, new_state

    return noisy


def init_state(mode: NoiseMode, rng: Optional[jax.Array] = None):
    return mode.make_state(rng if rng is not None else jax.random.PRNGKey(0))


def step_region(name: str, step_fn: Callable, args: tuple,
                registry: dict[str, NoiseMode], *, body_size: int = 0,
                rng: Optional[jax.Array] = None):
    """Adapt a jitted step + graph-level noise registry into a RegionTarget:
    sweeps time the runtime-k build; the static-k build is the clean
    reference and the payload check's census.

    The region's payload check runs what it checks: it counts the surviving
    patterns of the static build at ``min(k, CHECK_K_MAX)`` (patterns are
    counted in unrolled HLO, which only a static k gives), runs that build
    once, and holds its outputs, and those of the runtime-k build at the
    swept k, to the runtime-k build's outputs at k=0, so the check compiles
    nothing for the reference that the sweep did not: injection must leave
    them bit-identical (``ref_err`` the worst relative difference,
    ``ref_tol`` 0)."""
    from repro.core.controller import RegionTarget   # cycle: controller->here

    rng = jax.random.PRNGKey(0) if rng is None else rng
    states = {m: registry[m].make_state(rng) for m in registry}

    def build(mode: str, k: int):
        if not mode or k == 0:
            return jax.jit(step_fn)
        return jax.jit(inject(step_fn, registry[mode], k))

    def args_for(mode: str, k: int):
        if not mode or k == 0:
            return args
        return (states[mode], *args)

    @functools.cache     # the payload check reuses the sweep's executable
    def build_rt(mode: str):
        return jax.jit(inject_rt(step_fn, registry[mode]))

    def args_for_rt(mode: str):
        return (states[mode], *args)

    def payload_check(mode: str, k: int) -> payload_mod.InjectionReport:
        from repro.kernels.region import CHECK_K_MAX
        from repro.spans import span

        k_swept, k = k, min(k, CHECK_K_MAX)
        with span("campaign.payload_check.static_run", k=k):
            static = build(mode, k).lower(*args_for(mode, k)).compile()
            out = static(*args_for(mode, k))[0]
            rep = payload_mod.analyze_injection(
                static.as_text(), mode=mode, target=registry[mode].target,
                expected=k)
        with span("campaign.payload_check.reference"):
            rt = build_rt(mode)
            clean = rt(jnp.int32(0), *args_for_rt(mode))[0]
            got = [out, rt(jnp.int32(k_swept), *args_for_rt(mode))[0]]
            err = max(outputs_err(g, clean) for g in got)
        return dataclasses.replace(rep, ref_err=err, ref_tol=0.0)

    return RegionTarget(name=name, build=build, args_for=args_for,
                        body_size=body_size,
                        payload_target={m: registry[m].target
                                        for m in registry},
                        build_rt=build_rt, args_for_rt=args_for_rt,
                        payload_check=payload_check,
                        audit_hint={"scoped": True, "in_loop": False})


def outputs_err(got, want) -> float:
    """The worst max|got - want| / max|want| over the leaves of two output
    trees, reduced on the device (only the scalars reach the host)."""
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        g, w = jnp.asarray(g, jnp.float32), jnp.asarray(w, jnp.float32)
        diff = float(jnp.max(jnp.abs(g - w)))
        worst = max(worst, diff / max(float(jnp.max(jnp.abs(w))), 1e-30))
    return worst


def verify_semantics(step_fn: Callable, args: tuple, mode: NoiseMode,
                     k: int = 8, *, rtol: float = 0.0, atol: float = 0.0
                     ) -> bool:
    """Paper §2.3 property: injection must not change program semantics.
    Checks the wrapped output equals the clean output (bitwise by default)."""
    clean = jax.jit(step_fn)(*args)
    state0 = init_state(mode)
    noisy_out, _, _ = jax.jit(inject(step_fn, mode, k))(state0, *args)
    ok = True

    def chk(a, b):
        nonlocal ok
        import numpy as np
        a = np.asarray(a)
        b = np.asarray(b)
        if rtol == 0.0 and atol == 0.0:
            ok = ok and bool((a == b).all() or
                             (np.isnan(a) & np.isnan(b)).all())
        else:
            ok = ok and bool(np.allclose(a, b, rtol=rtol, atol=atol))

    jax.tree.map(chk, clean, noisy_out)
    return ok
