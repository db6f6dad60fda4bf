"""Device time of entry selection (the XLA module whose name holds
``select_entries``: query tower, hub scores, argmax, hub ids) per query
answered in the traced window.  From the profiler trace."""
import devtrace


def read(ctx):
    red = ctx["trace"]
    queries = sum(len(r.queries) for r in ctx["window"].records if r.answered)
    if red is None or not queries:
        return None
    inside, _ = devtrace.module_seconds(red, "select_entries")
    return 1e6 * inside / queries if inside > 0 else None
