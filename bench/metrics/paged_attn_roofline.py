"""The Pallas paged-attention decode kernel's share of its roofline:
the least time the chip needs for the attention the decoded tokens
require (bytes over HBM bandwidth, or FLOPs over peak, whichever is
larger, per quantum) over the kernel's summed device time in the trace.
Bytes and FLOPs count only the positions each token attends.

A traced window that decoded tokens but holds no operation of the
kernel's name is an error, not a silent omission: the kernel was renamed
or wrapped, or left the path, and this reader has to follow it."""
import re

from bench.counts import attention_flops, decode_attention_bytes

# the Pallas call's HLO name in the trace, e.g. paged_flash_decode_gqa.8
KERNEL = re.compile(r"^paged_flash_decode_gqa\b")


def kernel_seconds(trace) -> float:
    return sum(v for k, v in trace.op_seconds.items() if KERNEL.search(k))


def read(rec):
    if rec.trace is None:
        return None
    steps = rec.traced_steps()
    if not any(s.decode_tokens for s in steps):
        return None
    t_kernel = kernel_seconds(rec.trace)
    if t_kernel <= 0:
        raise LookupError(f"no device operation named like {KERNEL.pattern}"
                          " in a trace of decode steps")
    bw = rec.peaks["hbm_bytes_per_s"]
    peak = rec.peaks["bf16_flops_per_s"]
    least = 0.0
    for s in steps:
        if s.decode_tokens:
            least += max(
                decode_attention_bytes(rec.shape, s.decode_keys,
                                       s.decode_tokens) / bw,
                attention_flops(rec.shape, s.decode_keys) / peak)
    return 100.0 * least / t_kernel
