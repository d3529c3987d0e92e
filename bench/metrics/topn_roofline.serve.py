"""Top-N kernel (kernels/bpmf_topn.py): the least time the chip could take
for the window's kernel calls, each max(FLOPs / peak, bytes / HBM
bandwidth) from its shapes (bench/work.py), over the device time of the
kernel's events in the trace. At S*K = 1024 and 27,278 items every call is
bound by reading V' (bytes), not by FLOPs."""
import tracereduce
import work

KERNEL = "topn_scores_pallas"


def read(info):
    tr, window = info["trace"], info["window"]
    calls = info["layer"].get("topn_calls")
    if tr is None or window is None or not calls:
        return None
    secs, count = tracereduce.kernel_seconds(tr, window, KERNEL)
    if count == 0 or secs <= 0:
        return None
    peaks = info["peaks"]
    least = sum(max(work.topn_flops(b, w, n) / peaks["flops_per_s"],
                    work.topn_bytes(b, w, n) / peaks["hbm_bytes_per_s"])
                for b, w, n in calls)
    return 100.0 * least / secs
