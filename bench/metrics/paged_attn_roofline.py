"""The Pallas paged-attention decode kernel's share of its roofline:
the least time the chip needs for the attention the decoded tokens
require (bytes over HBM bandwidth, or FLOPs over peak, whichever is
larger, per quantum) over the kernel's summed device time in the trace.
Bytes and FLOPs count only the positions each token attends; the counts
and the kernel's name are the configuration's model module's.

A traced window that decoded tokens but holds no operation of the
kernel's name is an error, not a silent omission: the kernel was renamed
or wrapped, or left the path, and this reader has to follow it."""
import re

from bench import manifest


def kernel_seconds(trace, pattern: str) -> float:
    """Device seconds, summed over the chips, of the operations whose HLO
    instruction name matches ``pattern``."""
    kernel = re.compile(pattern)
    return sum(v for k, v in trace.op_seconds.items() if kernel.search(k))


def read(rec):
    if rec.trace is None:
        return None
    steps = rec.traced_steps()
    if not any(s.decode_tokens for s in steps):
        return None
    model = manifest.model(rec.config)
    t_kernel = kernel_seconds(rec.trace, model.ATTENTION_KERNEL)
    if t_kernel <= 0:
        raise LookupError(f"no device operation named like "
                          f"{model.ATTENTION_KERNEL} in a trace of decode "
                          f"steps")
    bw = rec.peaks["hbm_bytes_per_s"]
    peak = rec.peaks["bf16_flops_per_s"]
    least = 0.0
    for s in steps:
        if s.decode_tokens:
            least += max(
                model.decode_attention_bytes(rec.shape, s.decode_keys,
                                             s.decode_tokens) / bw,
                model.attention_flops(rec.shape, s.decode_keys) / peak)
    return 100.0 * least / t_kernel
