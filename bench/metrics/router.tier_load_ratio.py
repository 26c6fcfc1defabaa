"""How evenly the router spread the decoding over its tiers: the most
output tokens one tier decoded in the window's steps over the mean of all
tiers (StepReport.decoded of each tier's steps; a tier that never stepped
decoded none). 1.0 is an even spread; one idle tier of four gives 4/3.
None for a single engine."""


def read(rec):
    if len(rec.tiers) < 2:
        return None
    decoded = dict.fromkeys(rec.tiers, 0)
    for s in rec.window_steps():
        for tier, n in s.per_tier.items():
            decoded[tier] += n
    mean = sum(decoded.values()) / len(decoded)
    return max(decoded.values()) / mean if mean > 0 else None
