"""Share of the allocated cache that the decoding lanes held live, a wave:
the difference of two `ContinuousEngine.stats()["cache"]` snapshots
(`live_bytes_sum` of every kind, summed per decode wave from the lanes'
lengths) over `decode_iterations` x the bytes allocated, in percent. What
uniform `max_len` rows cost a model whose cache is mostly rings and state.
A program whose `stats()` has no `cache` entry reads as None."""


def read(params, ctx):
    c = ctx["counters"]
    live, allocated = c.get("cache_live_bytes_sum"), c.get("cache_bytes")
    if live is None or not allocated or not c.get("decode_iterations"):
        return None
    return 100.0 * live / (c["decode_iterations"] * allocated)
