"""Device time of the search program (the ``_batched_search`` module: the
vmapped hop loop and its distance path) per query answered in the traced
window.  From the profiler trace."""
import devtrace


def read(ctx):
    red = ctx["trace"]
    queries = sum(len(r.queries) for r in ctx["window"].records if r.answered)
    if red is None or not queries:
        return None
    inside, _ = devtrace.module_seconds(red, "_batched_search")
    return 1e6 * inside / queries if inside > 0 else None
