"""prefill_padding.prefill: the share of the positions prefilled in the
traced window that are padding, (sum of buckets - sum of prompts) / sum
of buckets, where each prompt runs at the engine's compile bucket for its
length (``Engine._prefill_bucket``)."""


def read(trace, facts):
    buckets = sum(facts["prefill_buckets"])
    if not buckets:
        return None
    return 100.0 * (buckets - sum(facts["prefill_lens"])) / buckets
