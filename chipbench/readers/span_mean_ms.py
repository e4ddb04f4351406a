"""Mean duration of the request-scale spans of the requests that RETIRE in
the traced interval, in ms: spans of different requests overlap without
nesting (a request's wait for a slot, its prefill), so `span_ms`, which
unions the spans of one thread, cannot read them.

The program notes such a span when its request retires, as Chrome's async
pair: the `e` event stands at the span's end and carries `dur`, and its
`retired_us` says when the request retired (a wait for a slot ends long
before its request does: in the long-generation cells no span both ends
and is noted inside a 3 s interval; a span without `retired_us` counts by
its own end). They come through the program's public accessor
(`incubator_mxnet_tpu.profiler.events()`). The interval is `span_ms`'s:
from `skip_head_s` after the buffer's first complete span (the session's
start) for `ctx["window_s"]`. Params: `name` (a regular expression on the
span's name), `skip_head_s`. None where the program records no such span
(the parent of the PR that added this reader)."""
import re

from .span_ms import spans


def ended():
    """[(name, retired or end us, dur_us)] of the program's async spans,
    or [] where the program has no such accessor or records none."""
    try:
        from incubator_mxnet_tpu import profiler
        events = profiler.events()
    except (ImportError, AttributeError):
        return []
    return [(e["name"], e.get("args", {}).get("retired_us", e["ts"]),
             e["dur"]) for e in events
            if e.get("ph") == "e" and "dur" in e]


def reduce(complete, async_ends, params, window_s):
    """The metric from `span_ms.spans()`'s complete spans (they place the
    interval) and [(name, retired or end us, dur_us)]."""
    if not complete or not async_ends:
        return None
    lo = min(s[2] for s in complete) + 1e6 * params.get("skip_head_s", 0.0)
    hi = lo + 1e6 * window_s
    name = re.compile(params["name"])
    kept = [dur for n, end, dur in async_ends
            if lo <= end <= hi and name.search(n)]
    if not kept:
        return None
    return 1e-3 * sum(kept) / len(kept)


def read(params, ctx):
    return reduce(spans(), ended(), params, ctx["window_s"])
