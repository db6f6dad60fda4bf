"""One run of one cell: set-up, the measured window, the check, the result.

Set-up loads the cell's index from the cache (building it on a miss), starts
``ServeDaemon`` at the configuration's rung with adaptation off, and warms
the one search program and request shape the cell's traffic uses.  The
window drives ``ServeDaemon.submit`` with the traffic mix.  After the window
the device's peak memory is read, the server is stopped and its state freed,
and every answer served is compared with the plain reference.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import time
from typing import Callable, Optional

import numpy as np

import check
import index_cache
import spec
import traffic as traffic_mod

WARM_REQUESTS = 2


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and (info["platform"] != "tpu" or info["count"] < chips):
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX found "
                         f"{info['count']} {info['platform']} device(s)")
    return info


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``bench/.jax_cache`` in the checkout; every
    program is kept, however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, "bench", ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def peak_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Session:
    """The server of one cell, set up once; ``window`` drives it.

    ``fault`` is applied to the loaded index before serving (a test breaks
    the timed path with it)."""

    def __init__(self, cell: spec.Cell, *, root: str = spec.ROOT,
                 require_tpu: bool = True, fault: Optional[Callable] = None):
        from repro.graphs.params import SearchParams
        from repro.obs import LadderRung
        from repro.serve.daemon import ServeDaemon

        self.config, self.mix = cell.config, cell.traffic
        self.device = device_info(cell.chips, require_tpu)
        log(f"device: {self.device}")
        log(f"compile cache: {enable_compile_cache(root)}")
        self.peaks = (spec.load_peaks(self.device["kind"], root)
                      if require_tpu else None)
        self.compiles = CompileCounter()
        index, self.build_s = index_cache.load_or_build(self.config, root)
        if fault is not None:
            fault(index)
        self.index = index
        self.db = index.db
        self.R = int(index.neighbors.shape[1])
        self.qpr = int(self.mix["queries_per_request"])
        rung = LadderRung(**self.config["rung"])
        self.params = SearchParams(
            k=self.config["k"], metric=self.config["metric"],
            kernel=self.config["kernel"], instrument=True)
        self.daemon = ServeDaemon(
            index, ladder=(rung,), adaptive=False, level=0,
            batch_size=self.qpr, k=self.config["k"],
            kernel=self.config["kernel"])
        index.warmup_ladder((rung,), batch_size=self.qpr, params=self.params)
        self.daemon.start(warmup=False)
        gen = spec.generator(self.config["generator"], root)
        self.maker = gen.query_maker(self.db, self.mix["query_kind"],
                                     self.config)

    def submit(self, queries: np.ndarray):
        from repro.serve.daemon import SearchRequest

        return self.daemon.submit(SearchRequest(
            queries=queries, k=self.config["k"], params=self.params))

    def window(self, seed: int, seconds: float,
               trace_dir: Optional[str] = None) -> traffic_mod.Window:
        """Warm requests, then the traffic of ``seed`` for ``seconds``; with
        ``trace_dir``, under the profiler.  Traffic made before the window
        starts."""
        import jax

        seed %= 2 ** 63      # any whole number, negative ones too
        warm = np.random.default_rng([seed, 99])
        for _ in range(WARM_REQUESTS):
            self.submit(self.maker.make(warm, self.qpr)).get(600.0)
        mix = self.mix
        if mix["loop"] == "open":
            offsets = traffic_mod.open_offsets(
                float(mix["rate_rps"]), seconds,
                np.random.default_rng(int(mix["schedule_seed"])))
            rng = np.random.default_rng([seed, 0])
            requests = [self.maker.make(rng, self.qpr) for _ in offsets]
        else:
            rngs = [np.random.default_rng([seed, 1, c])
                    for c in range(int(mix["clients"]))]
        self.ready_at = time.perf_counter()
        self.compiles.count = 0
        self.compiles.active = True
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(
                    "bench.window"):  # the traced window, for devtrace
                if mix["loop"] == "open":
                    return traffic_mod.drive_open(self.submit, requests,
                                                  offsets, seconds)
                return traffic_mod.drive_closed(
                    self.submit, lambda c, i: self.maker.make(rngs[c],
                                                              self.qpr),
                    int(mix["clients"]), seconds)
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
            self.compiles.active = False

    def close(self) -> None:
        """Stop the server and drop its device state."""
        self.daemon.stop()
        self.daemon = None
        self.index._dev = None
        self.index = None
        gc.collect()


def judge_window(win: traffic_mod.Window, ref, config: dict,
                 answers=None) -> dict:
    """The numbers compared for the answers served in ``win`` (or for
    ``answers(queries) -> (ids, dists)`` on the same queries), with
    ``correct`` and the rows printed beside their limits."""
    k = config["k"]
    queries, ids, dists = check.served(win.records)
    if answers is not None:
        ids, dists = answers(queries)
    true_ids, true_d = ref.topk(queries, k)
    ref_d = ref.distances(queries, np.where(ids >= 0, ids, 0))
    numbers = check.compare(queries, ids, dists, true_ids, true_d, ref_d,
                            ref.n, k, config["metric"],
                            ref.row_norms(true_ids[:, k - 1]))
    numbers["unanswered"] = sum(1 for r in win.records if not r.answered)
    correct, rows = check.judge(numbers, config)
    return {"numbers": numbers, "correct": correct, "rows": rows}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: str = spec.ROOT, require_tpu: bool = True,
             fault: Optional[Callable] = None) -> dict:
    """One run; returns the result object (keys starting with ``_`` are for
    the caller, not the result line).  Its ``index`` says whether this run
    built the index, so that its ``setup_s`` holds the build, or loaded it
    from the cache."""
    import reference

    s = Session(cell, root=root, require_tpu=require_tpu, fault=fault)
    trace_dir = (os.path.join(root, "bench", ".trace", f"{cell.name}-{seed}")
                 if trace else None)
    win = s.window(seed, seconds, trace_dir)
    setup_s = s.ready_at - t_start
    memory_peak = peak_bytes()
    compiles = s.compiles.count
    config, db, R, peaks, device = s.config, s.db, s.R, s.peaks, s.device
    s.close()
    index = {"state": "cached" if s.build_s is None else "built",
             "build_s": s.build_s}
    log(f"setup {setup_s:.3f} s (index {index['state']})")
    answered = [r for r in win.records if r.answered]
    log(f"window {win.seconds:.3f} s: {len(win.records)} requests, "
        f"{len(answered)} answered, {compiles} compiles")
    if len(win.late):
        log(f"generator lateness: mean {1e3 * win.late.mean():.3f} ms, "
            f"max {1e3 * win.late.max():.3f} ms")

    t0 = time.perf_counter()
    verdict = judge_window(win, reference.Reference(db, config["metric"]),
                           config)
    log(f"reference: {sum(len(r.queries) for r in answered)} queries in "
        f"{time.perf_counter() - t0:.2f} s")

    ctx = {"window": win, "config": config, "traffic": cell.traffic,
           "peaks": peaks, "R": R, "numbers": verdict["numbers"],
           "trace": None}
    if trace:
        import devtrace

        t0 = time.perf_counter()
        ctx["trace"] = devtrace.reduce(devtrace.load(
            devtrace.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t0:.2f} s")
        metrics = spec.read_metrics(cell.per_layer, ctx, root)
    else:
        metrics = end_to_end(cell, ctx, setup_s)
    result = {
        "correct": bool(verdict["correct"]),
        "attempted": len(win.records),
        "failed": verdict["numbers"]["unanswered"],
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": memory_peak},
    }
    if trace:
        red = ctx["trace"]
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
    result["index"] = index
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in verdict["rows"]}
    result["_rows"] = verdict["rows"]
    result["_compiles_in_window"] = compiles
    return result


def end_to_end(cell: spec.Cell, ctx: dict, setup_s: float) -> dict:
    """The cell's end-to-end metrics: the rate over all the work and all the
    time of the window, and the recall of every answer served."""
    win = ctx["window"]
    done = sum(len(r.queries) for r in win.records if r.answered)
    values = {
        "setup_s": setup_s,
        "recall_at_10": ctx["numbers"]["recall_at_10"],
        "qps": done / win.seconds,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
