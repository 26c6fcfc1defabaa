"""Model FLOPs of the useful work in the window's steps over the steps'
time and the chips' bf16 peak. Useful: prompt tokens prefilled (padding
excluded), each with its causal attention, and every decoded token with
the positions it attends; logits for each served token. The counts are
the configuration's model module's."""
from bench import manifest


def read(rec):
    steps = rec.window_steps()
    span = sum(s.t1 - s.t0 for s in steps)
    if span <= 0:
        return None
    dec = sum(s.decode_tokens for s in steps)
    firsts = sum(s.emitted for s in steps) - dec
    flops = manifest.model(rec.config).model_flops(
        rec.shape, sum(s.prefill_tokens for s in steps) + dec,
        sum(s.prefill_keys + s.decode_keys for s in steps), dec + firsts)
    return 100.0 * flops / (span * rec.chips * rec.peaks["bf16_flops_per_s"])
