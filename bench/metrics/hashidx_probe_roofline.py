"""HBM-roofline share of the hash-index probe kernel (``_probe_kernel``)
in the traced window, in %: per query two (8, bucket_cap) int32 tiles
read and its candidate and hit rows written (bench/peaks.py
``probe_bytes``), over the chip's HBM bandwidth, divided by the
kernel's measured time."""
from bench import devtrace, peaks


def read(ctx):
    tr, pk = ctx.get("trace"), ctx.get("peaks")
    calls = devtrace.kernel_calls(tr, "_probe_kernel") if tr else []
    if not calls or pk is None:
        return None
    need = sum(peaks.probe_bytes(p["queries"], p["bucket_cap"],
                                 p["tables"], p["outputs"])
               for _, p in calls)
    t = sum(d for d, _ in calls) * 1e-9
    return 100.0 * need / pk["hbm_bytes_per_s"] / t
