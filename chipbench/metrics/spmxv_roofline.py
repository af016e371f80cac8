"""The SPMXV kernel's share of its roofline at k=0: the compulsory bytes
(values, column indices, x and y once) over the HBM bandwidth, divided by
the kernel's device time per call. A call is one program execution that
starts inside the traced run's k=0 span and runs the kernel; its time is
the sum of its kernel events (the trace can split one call's kernel into
several events, and can put a call at the span's edge outside it, so calls
are counted by program, not assumed). The kernel is bound by bytes."""

from chipbench.counts import spmxv_ell_bytes
from chipbench.trace import is_kernel


def read(run):
    tr = run.trace
    if tr is None or not run.peaks or not tr.programs or not tr.ops:
        return None
    spans = tr.spans_named("bench.kernel_k0")
    dev = min(tr.programs)
    kernels = [e for e in tr.ops.get(dev, []) if is_kernel(e)]
    per_call = []
    for p in tr.programs[dev]:
        if not any(s.start <= p.start <= s.end for s in spans):
            continue
        t = sum(e.seconds for e in kernels if p.start <= e.start <= p.end)
        if t > 0:
            per_call.append(t)
    if not per_call:
        return None
    least = spmxv_ell_bytes(run.counters["rows"],
                            run.counters["nnz_per_row"]) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least * len(per_call) / sum(per_call)
