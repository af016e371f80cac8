"""``pallas_region`` — RegionTargets over the Pallas kernel layer.

The loop-level (``loop_region``) and graph-level (``step_region``) injection
sites have ridden the Controller/Campaign spine since PR 1; this adapter puts
the instruction-granularity Pallas kernels on the same spine:

  * ``build(mode, k)``    — one static-k executable (k=0 reference, audit);
  * ``build_rt(mode)``    — ONE runtime-k executable per (kernel, mode,
    static block values, argument shapes) in the process: the noise
    quantity is a scalar-prefetch operand of the kernel (noise_slots
    runtime-k protocol), so ``Controller.run_mode`` sweeps a whole k-grid
    AND checks its payload on that one executable instead of one per k —
    the paper's "Fast: ✗" concession, escaped at the last layer that still
    paid it. The jitted callable takes every buffer as an argument, so
    same-shaped regions (a family's q values, the regions of a later
    campaign) share it, and only a new shape traces, lowers and loads;
  * campaigns persist/replay (region, mode, k, t) records for Pallas regions
    exactly like any other RegionTarget — a completed Pallas campaign
    replays with zero new measurements;
  * payload verification works at the arithmetic level: instead of counting
    surviving scope-tagged HLO ops (Pallas bodies carry no ``named_scope``
    metadata through lowering, and the runtime-k HLO holds one pattern in a
    loop), the check runs the runtime-k executable the sweep timed once and
    compares ``nacc`` against the exact per-mode oracle — stronger than op
    counting, since the accumulated value pins both that ALL k patterns
    executed and that none was duplicated, in the executable measured.

Backends (``repro.kernels.backend``): "pallas" compiles the kernel
with Mosaic and refuses to build a region where JAX finds no TPU;
"interpret" runs the kernel body in the Pallas interpreter, for tests and
docs. On the chip the payload check also holds the kernel's main output to
a float32 reference (``REF_TOL``), and the static audit leaves the region
out: the Mosaic body is one opaque ``tpu_custom_call`` in the HLO.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controller import RegionTarget
from repro.core.payload import InjectionReport
from repro.kernels import noise_slots as ns
from repro.kernels.backend import use_interpreter
from repro.kernels.flash_attention.kernel import (flash_attention_pallas,
                                                  flash_attention_pallas_rt)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.noise_probes.kernel import probe_pallas, probe_pallas_rt
from repro.kernels.noise_probes.ref import probe_ref
from repro.kernels.noisy_matmul.kernel import matmul_pallas, matmul_pallas_rt
from repro.kernels.noisy_matmul.ops import default_noise_operand
from repro.kernels.spmv_ell.kernel import spmv_ell_pallas, spmv_ell_pallas_rt
from repro.kernels.spmv_ell.ref import (fp_noise_ell_ref, make_band_ell,
                                        vmem_noise_ell_ref)
from repro.spans import span

# noise modes each kernel supports (spmv has no VMEM noise operand -> no mxu)
KERNEL_MODES = {
    "matmul": ("fp", "mxu", "vmem"),
    "spmxv": ("fp", "vmem"),
    "attention": ("fp", "mxu", "vmem"),
    "probe": ("fp", "mxu", "vmem"),
}

# per-kernel meaning of the one "size" knob a family sweeps, its default, and
# the block width it must tile (sizes below one block are allowed: the block
# shrinks; 'probe' counts grid steps — any positive size is fine)
SIZE_KW = {"matmul": "n", "spmxv": "n", "attention": "seq", "probe": "n_steps"}
SIZE_DEFAULT = {"matmul": 256, "spmxv": 512, "attention": 128, "probe": 64}
SIZE_ALIGN = {"matmul": 128, "spmxv": 128, "attention": 64, "probe": 1}


def validate_size(kernel: str, n: int) -> None:
    """The size rule every entry point (probe CLI, fleet plans, families)
    shares: noise patterns read 8-row groups, and sizes past one block must
    tile evenly."""
    if kernel not in SIZE_KW:
        raise ValueError(f"unknown pallas kernel {kernel!r}; "
                         f"one of {sorted(SIZE_KW)}")
    align = SIZE_ALIGN[kernel]
    if n < 1:
        raise ValueError(f"size for {kernel!r} must be positive; got {n}")
    if align > 1 and (n < 8 or (n > align and n % align)):
        raise ValueError(
            f"size for {kernel!r} must be >= 8 and a multiple of its "
            f"{align}-wide block (or smaller than one block); got {n}")

# which resource one pattern of each kernel mode stresses (payload reports)
MODE_TARGETS = {"fp": "compute", "mxu": "compute", "vmem": "vmem"}

# max|out - ref| / max|ref| the payload check allows the kernel's main
# output against its float32 reference. The MXU kernels ask Mosaic for
# float32 products (Precision.HIGHEST); a single bf16 pass of the same
# operands reads ~2e-3, so 1e-4 tells the two apart. SPMXV is f32 VPU work
REF_TOL = {"matmul": 1e-4, "spmxv": 1e-5, "attention": 1e-4}

# the payload check counts at most this many patterns: its nacc oracle
# holds only up to float32 summation error, which grows with the adds one
# accumulator lane takes (k x grid steps)
CHECK_K_MAX = 16


def oracle_rtol(n_adds: int) -> float:
    """Relative tolerance of an nacc oracle after ``n_adds`` sequential
    float32 adds: the recursive-summation bound n·u, u = 2^-24. At
    ``CHECK_K_MAX`` it stays below the 1/k a missing pattern would cost."""
    return max(1e-4, n_adds * 2.0 ** -24)


# region-name derivation, shared by the spec builders below and by
# ``family_names`` (cheap grid queries — fleet status/inspect must learn a
# family's region names without building a single jax array). Defaults here
# mirror the builder signatures; ``test_pallas_region`` pins the agreement.
def _matmul_name(*, n=256, **_):
    return f"pallas_matmul_n{n}"


def _spmxv_name(*, n=512, nnz_per_row=16, q=0.0, **_):
    return f"pallas_spmxv_n{n}_L{nnz_per_row}_q" + f"{q:g}".replace(".", "p")


def _attention_name(*, batch=1, heads=2, seq=128, head_dim=64, **_):
    return f"pallas_attn_b{batch}h{heads}s{seq}d{head_dim}"


def _probe_name(*, n_steps=64, **_):
    return f"pallas_probe_s{n_steps}"


_NAMERS = {"matmul": _matmul_name, "spmxv": _spmxv_name,
           "attention": _attention_name, "probe": _probe_name}


@dataclasses.dataclass(frozen=True)
class _KernelSpec:
    """Everything ``pallas_region`` needs about one kernel: its arguments,
    its static-k and runtime-k callables, and the exact nacc oracle."""
    name: str
    args: tuple
    static_fn: Callable[[str, int], Callable]   # (mode, k) -> fn(*args)
    # module-level fn(k, *args, mode=, **rt_static), and the static values
    # it takes besides the mode, as sorted (keyword, value) pairs
    rt_kernel: Callable
    rt_static: tuple
    oracle: Callable[[str, int], Optional[jnp.ndarray]]
    n_steps: int                                # grid steps visiting the slot
    body_size: int                              # |l1.l2| stand-in for Abs^rel
    # () -> float32 reference of the main output; None: no main output
    reference: Optional[Callable[[], np.ndarray]] = None

    def rt_fn(self, mode: str) -> Callable:
        """fn(k, *args): the runtime-k kernel with its static values bound,
        closing over no buffer."""
        return functools.partial(self.rt_kernel, mode=mode,
                                 **dict(self.rt_static))


def _statics(**values) -> tuple:
    return tuple(sorted(values.items()))


def _matmul_spec(interpret: bool, *, n: int = 256, bm: int = 128,
                 bn: int = 128, bk: int = 128) -> _KernelSpec:
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
    noise = default_noise_operand()
    bm, bn, bk = min(bm, n), min(bn, n), min(bk, n)
    grid_steps = (n // bm) * (n // bn) * (n // bk)

    def static_fn(mode, k):
        return lambda a, b, noise: matmul_pallas(
            a, b, noise, mode=mode, k_noise=k, bm=bm, bn=bn, bk=bk,
            interpret=interpret)

    def oracle(mode, k):
        if mode == "fp":
            return ns.expected_fp_noise(noise, k, grid_steps)
        return None

    def reference():
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)

    return _KernelSpec(_matmul_name(n=n), (a, b, noise), static_fn,
                       matmul_pallas_rt,
                       _statics(bm=bm, bn=bn, bk=bk, interpret=interpret),
                       oracle, grid_steps, body_size=3,
                       reference=reference)


def _spmxv_spec(interpret: bool, *, n: int = 512, nnz_per_row: int = 16,
                q: float = 0.0, br: int = 128, seed: int = 0) -> _KernelSpec:
    vals, cols = make_band_ell(n, nnz_per_row, q, seed=seed)
    x = jnp.asarray(np.random.RandomState(seed + 1)
                    .standard_normal(n).astype(np.float32))
    br = min(br, n)
    nb = n // br

    def static_fn(mode, k):
        return lambda vals, cols, x: spmv_ell_pallas(
            vals, cols, x, br=br, mode=mode, k_noise=k, interpret=interpret)

    def oracle(mode, k):
        if mode == "fp":
            return fp_noise_ell_ref(vals, k, br)
        if mode == "vmem":
            return vmem_noise_ell_ref(vals, k, br)
        return None

    def reference():     # NumPy, float64 accumulation
        v, c, xx = (np.asarray(t) for t in (vals, cols, x))
        return (v.astype(np.float64) * xx.astype(np.float64)[c]).sum(axis=1)

    return _KernelSpec(_spmxv_name(n=n, nnz_per_row=nnz_per_row, q=q),
                       (vals, cols, x), static_fn, spmv_ell_pallas_rt,
                       _statics(br=br, interpret=interpret), oracle, nb,
                       body_size=4, reference=reference)


def _attention_spec(interpret: bool, *, batch: int = 1, heads: int = 2,
                    kv_heads: int = 2, seq: int = 128, head_dim: int = 64,
                    bq: int = 64, bk: int = 64, causal: bool = True
                    ) -> _KernelSpec:
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (batch, heads, seq, head_dim), jnp.float32)
    k = jax.random.normal(keys[1], (batch, kv_heads, seq, head_dim),
                          jnp.float32)
    v = jax.random.normal(keys[2], (batch, kv_heads, seq, head_dim),
                          jnp.float32)
    noise = default_noise_operand()
    bq, bk = min(bq, seq), min(bk, seq)
    # only LIVE kv blocks visit the noise slot (causal skip)
    nq, nk = seq // bq, seq // bk
    live = sum(1 for qi in range(nq) for ki in range(nk)
               if not causal or ki * bk <= qi * bq + bq - 1)
    grid_steps = batch * heads * live

    def static_fn(mode, kn):
        return lambda q, k, v, noise: flash_attention_pallas(
            q, k, v, noise, causal=causal, bq=bq, bk=bk, mode=mode,
            k_noise=kn, interpret=interpret)

    def oracle(mode, kn):
        if mode == "fp":
            return ns.expected_fp_noise(noise, kn, grid_steps)
        return None

    def reference():
        with jax.default_matmul_precision("highest"):
            return jax.jit(attention_ref, static_argnames="causal")(
                q, k, v, causal=causal)

    return _KernelSpec(_attention_name(batch=batch, heads=heads, seq=seq,
                                       head_dim=head_dim),
                       (q, k, v, noise), static_fn, flash_attention_pallas_rt,
                       _statics(causal=causal, bq=bq, bk=bk,
                                interpret=interpret),
                       oracle, grid_steps, body_size=12, reference=reference)


def _probe_spec(interpret: bool, *, n_steps: int = 64) -> _KernelSpec:
    noise = default_noise_operand()

    def static_fn(mode, k):
        return lambda noise: probe_pallas(
            noise, mode=mode, k_noise=k, n_steps=n_steps,
            interpret=interpret)

    def oracle(mode, k):
        return probe_ref(noise, mode=mode, k_noise=k, n_steps=n_steps)

    return _KernelSpec(_probe_name(n_steps=n_steps), (noise,), static_fn,
                       probe_pallas_rt,
                       _statics(n_steps=n_steps, interpret=interpret),
                       oracle, n_steps, body_size=1)


_SPECS = {
    "matmul": _matmul_spec,
    "spmxv": _spmxv_spec,
    "attention": _attention_spec,
    "probe": _probe_spec,
}


def _nacc_of(result):
    return result[-1] if isinstance(result, (tuple, list)) else result


def _jit(fn, trace_hook):
    if trace_hook is None:
        return jax.jit(fn)

    def counted(*args):
        trace_hook()
        return fn(*args)

    return jax.jit(counted)


# the runtime-k callables of every Pallas region in the process: one jitted
# callable per (kernel function, mode, static values, trace hook), to which
# jax's own cache adds the argument shapes and dtypes. It closes over no
# buffer, so regions whose programs are identical (a family's q values, the
# regions each later campaign resolves) run one executable, and only a new
# shape traces, lowers and loads. Entries live as long as the process.
_RT_SHARED: dict[tuple, Callable] = {}


def _shared_rt(region: str, kernel: str, spec: _KernelSpec, mode: str,
               trace_hook: Optional[Callable[[], None]]) -> Callable:
    """The shared runtime-k callable for ``region``'s ``mode``, resolved
    inside the regions layer's span; its ``reused`` stat says whether the
    callable already existed in the process."""
    key = (spec.rt_kernel, mode, spec.rt_static, trace_hook)
    fn = _RT_SHARED.get(key)
    with span("campaign.region", region=region, kernel=kernel, mode=mode,
              reused=fn is not None):
        if fn is None:      # setdefault: sweeps on a thread pool race here
            fn = _RT_SHARED.setdefault(key, _jit(spec.rt_fn(mode),
                                                 trace_hook))
    return fn


def pallas_region(kernel: str, *, backend: str = "pallas", name: str = "",
                  trace_hook: Optional[Callable[[], None]] = None,
                  **sizes) -> RegionTarget:
    """A RegionTarget over one Pallas kernel, ready for
    ``Controller.characterize`` / ``Campaign.sweep_mode``.

    ``trace_hook`` (tests): called once per Python trace of any executable
    this region builds — each jit compilation traces exactly once, so the
    hook counts compiled executables (one per (kernel, mode) sweep, payload
    check included). The runtime-k callable is shared with every region of
    the same kernel, mode, static values and hook, as it is between
    production regions.
    ``sizes``: forwarded to the kernel's spec builder (e.g. ``n=``, ``q=``).
    """
    if kernel not in _SPECS:
        raise ValueError(f"unknown pallas kernel {kernel!r}; "
                         f"one of {sorted(_SPECS)}")
    interpret = use_interpreter(backend)
    with span("campaign.region", region=name or _NAMERS[kernel](**sizes)):
        spec = _SPECS[kernel](interpret, **sizes)
    modes = KERNEL_MODES[kernel]

    def _check_mode(mode):
        if mode not in modes:
            raise ValueError(f"kernel {kernel!r} supports noise modes "
                             f"{modes}, not {mode!r}")

    def build(mode: str, k: int):
        if not mode or k == 0:
            return _jit(spec.static_fn("none", 0), trace_hook)
        _check_mode(mode)
        return _jit(spec.static_fn(mode, k), trace_hook)

    def args_for(mode: str, k: int):
        return spec.args

    @functools.cache     # one span per (region, mode), not per call
    def build_rt(mode: str):
        _check_mode(mode)
        return _shared_rt(name or spec.name, kernel, spec, mode, trace_hook)

    def args_for_rt(mode: str):
        return spec.args

    @functools.cache
    def reference() -> np.ndarray:        # once per region, every mode
        return np.asarray(spec.reference(), np.float64)

    def payload_check(mode: str, k: int) -> InjectionReport:
        """Arithmetic-level payload check on the runtime-k build the sweep
        timed, so it loads no executable of its own: run it once at
        ``min(k, CHECK_K_MAX)``; an exact oracle match of ``nacc`` (or a
        nonzero accumulator for modes without a closed-form oracle) proves
        all those patterns executed. The main output of that run, and of a
        run at ``k`` itself where ``k`` is larger, is compared with the
        float32 reference; ``ref_err`` is the worse."""
        k_swept, k = k, min(k, CHECK_K_MAX)
        fn = build_rt(mode)                # checks the mode
        # the benchmark's breakdown reports idle gaps under this span name
        with span("campaign.payload_check.static_run", k=k):
            result = fn(jnp.int32(k), *spec.args)
            nacc = np.asarray(_nacc_of(result), np.float32)
        with span("campaign.payload_check.oracle", mode=mode, k=k):
            want = spec.oracle(mode, k)
            if want is not None:
                ok = np.allclose(nacc, np.asarray(want, np.float32),
                                 rtol=oracle_rtol(k * spec.n_steps),
                                 atol=1e-5)
            else:
                ok = bool(np.abs(nacc).sum() > 0) if k else True
        ref_err = ref_tol = None
        if spec.reference is not None:
            with span("campaign.payload_check.reference"):
                ref = reference()
                swept = result if k_swept == k else \
                    fn(jnp.int32(k_swept), *spec.args)
                ref_err = max(
                    float(np.max(np.abs(np.asarray(out, np.float64)
                                        .reshape(ref.shape) - ref))
                          / max(float(np.max(np.abs(ref))), 1e-30))
                    for out in (result[0], swept[0]))
            ref_tol = REF_TOL[kernel]
        return InjectionReport(
            mode=mode, target=MODE_TARGETS[mode], expected=k,
            payload=k if ok else 0, overhead=0,
            payload_dynamic=k * spec.n_steps, body_ops=spec.body_size,
            ref_err=ref_err, ref_tol=ref_tol)

    return RegionTarget(name=name or spec.name, build=build,
                        args_for=args_for, body_size=spec.body_size,
                        payload_target=dict(MODE_TARGETS),
                        build_rt=build_rt, args_for_rt=args_for_rt,
                        payload_check=payload_check,
                        # Pallas bodies lose named-scope metadata in
                        # lowering: the audit censuses everything and lets
                        # the two-point k-delta isolate the noise; a Mosaic
                        # body is opaque to the census altogether
                        audit_hint={"scoped": False, "in_loop": True,
                                    "steps": spec.n_steps,
                                    "opaque": not interpret})


def family_params(kernel: str) -> frozenset:
    """Keyword params the kernel's spec builder accepts — the allowlist
    plan validation checks declarative params against."""
    import inspect

    sig = inspect.signature(_SPECS[kernel])
    return frozenset(p.name for p in sig.parameters.values()
                     if p.kind == p.KEYWORD_ONLY)


def check_family_args(kernel: str, sizes, qs, common: dict) -> None:
    """The family argument rules, shared by ``pallas_family``,
    ``family_names`` and SweepPlan validation — so a bad family is rejected
    when the plan is BUILT, not when a worker subprocess resolves it."""
    if kernel not in _SPECS:
        raise ValueError(f"unknown pallas kernel {kernel!r}; "
                         f"one of {sorted(_SPECS)}")
    if qs is not None and kernel != "spmxv":
        raise ValueError(f"qs= applies to the 'spmxv' kernel only, "
                         f"not {kernel!r}")
    allowed = family_params(kernel) - {SIZE_KW[kernel], "q"}
    bad = sorted(set(common) - allowed)
    if bad:
        raise ValueError(f"kernel {kernel!r} spec does not accept param(s) "
                         f"{bad}; allowed: {sorted(allowed)}")
    for n in sizes:
        validate_size(kernel, int(n))


def _family_grid(kernel: str, sizes, qs):
    for n in sizes:
        for q in (qs if qs is not None else (None,)):
            kw = {SIZE_KW[kernel]: int(n)}
            if q is not None:
                kw["q"] = float(q)
            yield kw


def family_names(kernel: str, sizes, *, qs=None, **common) -> list[str]:
    """The region names ``pallas_family(kernel, sizes, qs=qs, **common)``
    would produce, WITHOUT building a single jax array — what fleet
    status/inspect/launch use to enumerate a plan's grid cheaply."""
    check_family_args(kernel, sizes, qs, common)
    return [_NAMERS[kernel](**{**common, **kw})
            for kw in _family_grid(kernel, sizes, qs)]


def pallas_family(kernel: str, sizes, *, qs=None, backend: str = "pallas",
                  trace_hook: Optional[Callable[[], None]] = None,
                  **common) -> list[RegionTarget]:
    """One RegionTarget per size (× swap probability q for spmxv), sharing
    one campaign-store namespace.

    The grid a kernel's characterization really spans is a size/q FAMILY —
    fig4 sweeps matmul n, fig7 sweeps the spmxv (n, q) plane — and every
    member's spec encodes its coordinates in the region name, so a single
    campaign store (and a single fleet plan) holds the whole family's
    (region, mode, k, t) records side by side. ``sizes`` drives the kernel's
    size knob (``SIZE_KW``); ``qs`` is spmxv-only; ``common`` (e.g.
    ``nnz_per_row=``) is forwarded to every member's spec builder.
    """
    check_family_args(kernel, sizes, qs, common)
    out = [pallas_region(kernel, backend=backend, trace_hook=trace_hook,
                         **{**common, **kw})
           for kw in _family_grid(kernel, sizes, qs)]
    names = [r.name for r in out]
    if len(set(names)) != len(names):
        raise ValueError(f"family members collide in one store namespace: "
                         f"{names}")
    return out
