"""Shared by the SyncTest kernel readers: the fused kernel's device time
(its custom-call ops in the trace, per chip) per frame advanced, and its
share of the HBM roofline. HBM bounds it: the least time a batch can take
is the bytes it must move (benchmark/bytes_model.py) over the chip's HBM
bandwidth (benchmark/peaks.py); no VPU int32 peak is published, and the
kernel does no matrix work."""

from benchmark.bytes_model import synctest_batch_bytes
from benchmark.peaks import peaks
from benchmark.trace_reduce import is_kernel, op_seconds


def _kernel_s(run):
    if run.trace is None or "check_distance" not in run.raw:
        return None
    kernel_s = op_seconds(run.trace, is_kernel)
    return kernel_s if kernel_s > 0 else None


def kernel_us_per_frame(run):
    kernel_s, frames = _kernel_s(run), run.traced.get("frames")
    if kernel_s is None or not frames:
        return None
    return kernel_s * 1e6 / frames


def kernel_hbm_roofline(run):
    kernel_s, batches = _kernel_s(run), run.traced.get("batches")
    if kernel_s is None or not batches:
        return None
    r = run.raw
    per_batch = synctest_batch_bytes(
        r["entities"], r["players"], r["check_distance"],
        r["frames"] // r["batches"], r.get("entity_shards", 1),
    )
    least_s = batches * per_batch / peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
