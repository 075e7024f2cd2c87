"""HBM-roofline share of relscan's scan pass (``_scan_kernel``) in the
traced window, in %: the bytes the pass must move (bench/peaks.py
``scan_bytes``, from each call's operand shapes) over the chip's HBM
bandwidth, divided by the kernel's measured time. ``_compact_kernel``
is left out: it skips tiles with no match, so bytes counted from its
shapes would overstate what it moves."""
from bench import devtrace, peaks


def read(ctx):
    tr, pk = ctx.get("trace"), ctx.get("peaks")
    calls = devtrace.kernel_calls(tr, "_scan_kernel") if tr else []
    if not calls or pk is None:
        return None
    need = sum(peaks.scan_bytes(p["arrays"]) for _, p in calls)
    t = sum(d for d, _ in calls) * 1e-9
    return 100.0 * need / pk["hbm_bytes_per_s"] / t
