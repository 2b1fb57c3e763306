"""Set-up, the measured window, the check against the reference, the result.

Everything a cell needs is found by name from ``BENCHMARK.json`` at the
checkout root:

  * the workload entry names a configuration and a traffic mix;
  * the configuration's ``file`` names its data ``generator``
    (``chipbench/data/<generator>.py``) and its plain ``reference``
    (``chipbench/references/<reference>.py``);
  * the traffic mix is ``chipbench/traffic/<traffic>.json`` and names its
    ``search`` (``chipbench/searches/<search>.py``, the one module that
    drives the program: ``run`` makes one search, ``warm`` loads or
    compiles every program the window will run);
  * each per-layer metric is read by ``chipbench/metrics/<name>.py``;
  * the limits of the correctness check are ``chipbench/limits/<cell>.json``;
  * the chips' peaks are ``chipbench/peaks.json``, keyed by device kind.

So a cell, a configuration, a traffic mix or a metric is added by adding
files and entries, and no file that is there changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import statistics
import sys
import tempfile
import time
from pathlib import Path

PACKAGE = "chipbench"
WARMUP_STREAM = 1  # search keys: fold_in(split(seed key)[0], i); warm-up: [1]


# -- finding a cell ------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict

    def path(self, *parts: str) -> Path:
        return self.root / PACKAGE / Path(*parts)

    def module(self, kind: str, name: str):
        """``chipbench/<kind>/<name>.py``, loaded from this checkout."""
        path = self.path(kind, f"{name}.py")
        if not path.is_file():
            raise FileNotFoundError(f"{path} does not exist")
        spec = importlib.util.spec_from_file_location(f"{PACKAGE}_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def _for_cell(entries: list, name: str) -> list:
    return [e for e in entries if name in e.get("workloads", [name])]


def load_cell(root: Path, name: str) -> Cell:
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    wl = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads((root / PACKAGE / "traffic" / f"{wl['traffic']}.json").read_text())
    limits = json.loads((root / PACKAGE / "limits" / f"{name}.json").read_text())
    return Cell(root, name, int(wl["chips"]), config, traffic,
                _for_cell(spec["end_to_end"], name), _for_cell(spec["per_layer"], name),
                limits)


# -- set-up helpers ------------------------------------------------------------
class CompileClock:
    """Backend compiles, their seconds, and persistent-cache hits, from
    JAX's own monitoring events (a cache hit replaces a backend compile),
    and the name of every program JAX lowers to compile or to look up in
    the cache (its compile log)."""

    def __init__(self):
        import logging

        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.lowered: list[str] = []
        clock = self

        class _Names(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    clock.lowered.append(msg.split(" ")[1])

        jax.config.update("jax_log_compiles", True)
        log = logging.getLogger("jax._src.interpreters.pxla")
        log.addHandler(_Names())
        log.propagate = False  # the names are kept here, not printed

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache, where the program keeps it.

    ``repro.core.compile_cache.resolve_compile_cache`` chooses the
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``<checkout>/.jax_cache``. The benchmark then keeps every program,
    however short its compile, so only a cell's first run compiles.
    """
    import jax

    from repro.core.compile_cache import resolve_compile_cache

    cache_dir = resolve_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def require_chips(chips: int) -> dict:
    """The device stamp; exits non-zero unless JAX holds ``chips`` TPUs."""
    import jax

    backend = jax.default_backend()
    devs = jax.devices()
    if backend != "tpu" or len(devs) < chips:
        print(f"chipbench: needs {chips} TPU chip(s), JAX found {len(devs)} "
              f"{backend!r} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return device_stamp(devs[:chips])


def device_stamp(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def seed_keys(seed: int):
    """(data key, search key, warm-up key) from any whole-number seed."""
    import jax

    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    data_key, search_key = jax.random.split(key)
    streams = jax.random.split(search_key)
    return data_key, streams[0], streams[WARMUP_STREAM]


# -- the window ----------------------------------------------------------------
@dataclasses.dataclass
class SearchRecord:
    key: object
    result: object
    traced: bool = False
    records: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    histograms: dict = dataclasses.field(default_factory=dict)

    @property
    def n_visited(self) -> int:
        return len(self.result.visits)

    @property
    def n_candidates(self) -> int:
        return self.result.n_candidates

    def spans(self, name: str) -> list[dict]:
        return [r["args"] for r in self.records if r["ph"] == "X" and r["name"] == name]


@dataclasses.dataclass
class Window:
    searches: list
    wall_s: float
    shape: dict  # n, m, nnz of V
    peak: dict | None
    trace: dict | None


def run_window(search, v, search_key, traffic: dict, seconds: float, traced: bool) -> Window:
    """Closed loop: searches back to back until ``seconds`` have passed;
    the window ends when the last search that started has returned."""
    import contextlib

    import jax

    from repro.obs import Metrics, Tracer, use_metrics, use_tracer

    annotate = jax.profiler.TraceAnnotation if traced else (lambda _: contextlib.nullcontext())
    recs = []
    t0 = time.perf_counter()
    with annotate("chipbench_window"):
        while not recs or time.perf_counter() - t0 < seconds:
            key = jax.random.fold_in(search_key, len(recs))
            tracer, metrics = Tracer(), Metrics()
            scope = contextlib.ExitStack()
            if traced:
                scope.enter_context(use_tracer(tracer))
                scope.enter_context(use_metrics(metrics))
            with scope, annotate("chipbench_search"):
                result = search.run(v, key, traffic)
            rec = SearchRecord(key, result, traced)
            if traced:
                summary = metrics.summary()
                rec.records = tracer.events()
                rec.counters = summary.get("counters", {})
                rec.histograms = summary.get("histograms", {})
            recs.append(rec)
    return Window(recs, time.perf_counter() - t0, {}, None, None)


# -- the check -----------------------------------------------------------------
def compare_ksearch(result, ref_score, threshold: float) -> dict:
    """How one search's answer compares with the reference's.

    The reference scores every k the search scored at or above its
    ``k_optimal``: the largest of them at or above ``threshold`` is the
    reference's choice among the ks the search could have chosen (Binary
    Bleed prunes only ks below a selected one). ``mismatch`` is 1 when the
    two choices differ; ``diff`` is program minus reference of the score at
    the program's ``k_optimal``.
    """
    visited = {r.k: r.score for r in result.visits}
    k_prog = result.k_optimal
    ks = sorted(k for k in visited if k_prog is None or k >= k_prog)
    ref = {k: ref_score(k) for k in ks}
    k_ref = max((k for k in ks if ref[k] >= threshold), default=None)
    return {
        "mismatch": 0 if (k_prog is not None and k_prog == k_ref) else 1,
        # a search that chose no k, or a k it never scored, has no score there
        "diff": visited[k_prog] - ref[k_prog] if k_prog in visited else math.inf,
        "k_optimal": k_prog,
        "k_optimal_reference": k_ref,
        "reference_scores": ref,
    }


def control_ksearch(result, ref_score, control_score, threshold: float) -> dict:
    """``compare_ksearch`` with the control in the program's place.

    The control scores the ks the reference scores for ``result`` (those the
    search scored at or above its ``k_optimal``) and chooses among them as
    Binary Bleed would; that choice and its score are then compared with
    the reference exactly as a program's would be.
    """
    from types import SimpleNamespace

    k_prog = result.k_optimal
    ks = sorted(r.k for r in result.visits if k_prog is None or r.k >= k_prog)
    scores = {k: control_score(k) for k in ks}
    k_ctrl = max((k for k in ks if scores[k] >= threshold), default=None)
    visits = [SimpleNamespace(k=k, score=s) for k, s in scores.items()]
    return compare_ksearch(SimpleNamespace(visits=visits, k_optimal=k_ctrl), ref_score, threshold)


def memo(fn):
    """fn(k), computed once per k."""
    seen = {}

    def once(k):
        if k not in seen:
            seen[k] = fn(k)
        return seen[k]

    return once


def compared_numbers(per_search: list[dict], far_gap: float) -> dict:
    """The numbers a run is judged by, over every search of its window.

    ``diff`` is program minus reference of the score at the program's
    ``k_optimal`` (infinite for a search that found none). One search's
    score moves by a few thousandths in either direction with where its
    lanes stopped and which neighbour warm-started them; a lower precision
    moves it in every search. In about one search in a hundred a
    cold-started reference fit has not converged at the chosen k (the
    program's warm-started one has), so no number is judged by one search.

    * ``score_gap``: |median of diff|, a shift shared by most searches;
    * ``abs_gap``: median of |diff|, most searches off in either direction;
    * ``searches_off``: searches whose ``k_optimal`` is missing or differs
      from the reference's, or whose |diff| exceeds ``far_gap``: a fault
      in a minority of searches;
    * ``k_missing``: searches that returned no ``k_optimal`` (limit 0).
    """
    diffs = [c["diff"] for c in per_search]
    return {
        "score_gap": abs(statistics.median(diffs)),
        "abs_gap": statistics.median(abs(d) for d in diffs),
        "searches_off": sum(1 for c in per_search
                            if c["mismatch"] or not abs(c["diff"]) <= far_gap),
        "k_missing": sum(1 for c in per_search if c["k_optimal"] is None),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct iff none exceeds it."""
    compared, ok = {}, True
    for name, limit in limits["limits"].items():
        value = numbers[name]
        finite = math.isfinite(value)  # a search with no k_optimal has diff inf
        ok = ok and finite and value <= limit
        compared[name] = {"value": value if finite else None, "limit": limit}
    return ok, compared


def check_searches(searches, v, reference, traffic: dict, operands=None) -> list[dict]:
    """``compare_ksearch`` of every search, or ``control_ksearch`` with
    ``operands`` set (the reference at that precision as the control)."""
    threshold = traffic["select_threshold"]
    out = []
    for rec in searches:
        ref = memo(lambda k, key=rec.key: reference.score(v, key, k, traffic))
        if operands is None:
            out.append(compare_ksearch(rec.result, ref, threshold))
        else:
            ctrl = memo(lambda k, key=rec.key: reference.score(v, key, k, traffic, operands))
            out.append(control_ksearch(rec.result, ref, ctrl, threshold))
    return out


# -- the run -------------------------------------------------------------------
def _memory_peak(devs) -> int | None:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _trace_window(fn, root: Path):
    """Run ``fn`` under the profiler; returns (fn's value, reduced trace)."""
    import jax

    from chipbench.profile import find_xplane, load_events, reduce_events

    trace_root = Path(root) / ".chipbench_traces"
    trace_root.mkdir(exist_ok=True)
    with tempfile.TemporaryDirectory(dir=trace_root) as tmp:
        jax.profiler.start_trace(tmp)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        summary = reduce_events(load_events(find_xplane(tmp)))
    return out, summary


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             device: dict | None = None, log=sys.stderr) -> dict:
    """One run of a cell; returns the result line's object.

    ``device`` is the stamp of the chips the run holds (``require_chips``);
    None skips that look, for tests that drive a run on the CPU.
    """
    import jax
    import jax.numpy as jnp

    def info(**kv):
        print(json.dumps(kv), file=log, flush=True)

    cache_dir = use_compile_cache()
    clock = CompileClock()
    if device is None:
        device = device_stamp(jax.devices()[: cell.chips])
    generator = cell.module("data", cell.config["generator"])
    search = cell.module("searches", cell.traffic["search"])
    reference = cell.module("references", cell.config["reference"])
    readers = {m["name"]: cell.module("metrics", m["name"]) for m in cell.per_layer}

    data_key, search_key, warm_key = seed_keys(seed)
    v = generator.generate(data_key, **cell.config["params"])
    n, m = v.shape
    nnz = int(jnp.count_nonzero(v))
    lowered = len(clock.lowered)
    warm_k = search.warm(v, warm_key, cell.traffic)  # compiles what the window runs
    setup_s = time.perf_counter() - t0
    info(setup={"setup_s": setup_s, "compile_s": clock.seconds, "compiles": clock.compiles,
                "cache_hits": clock.cache_hits, "cache_dir": cache_dir, "v_shape": [n, m],
                "nnz": nnz, "warmup_k_optimal": warm_k,
                "warmup_lowered": len(clock.lowered) - lowered})

    lowered = len(clock.lowered)
    if trace:
        window, summary = _trace_window(
            lambda: run_window(search, v, search_key, cell.traffic, seconds, True), cell.root)
        window.trace = summary
    else:
        window = run_window(search, v, search_key, cell.traffic, seconds, False)
    lowered_in_window = clock.lowered[lowered:]
    devs = jax.devices()[: cell.chips]
    memory_peak = _memory_peak(devs)
    window.shape = {"n": n, "m": m, "nnz": nnz}
    peaks = json.loads(cell.path("peaks.json").read_text())
    window.peak = peaks.get(device["kind"])
    if window.peak is None:
        raise KeyError(f"chipbench/peaks.json has no entry for device kind {device['kind']!r}")
    info(window={"searches": len(window.searches), "wall_s": window.wall_s,
                 "compiles_in_window": len(lowered_in_window), "lowered_in_window": lowered_in_window,
                 "memory_peak_bytes": memory_peak,
                 "k_optimal": [s.result.k_optimal for s in window.searches],
                 "ks_visited": [s.n_visited for s in window.searches]})

    metrics = {}
    if trace:
        for m_ in cell.per_layer:
            value = readers[m_["name"]].read(window)
            if value is not None:
                metrics[m_["name"]] = {"value": value, "unit": m_["unit"]}
    else:
        by_name = {"search_s": window.wall_s / len(window.searches), "setup_s": setup_s}
        for m_ in cell.end_to_end:
            metrics[m_["name"]] = {"value": by_name[m_["name"]], "unit": m_["unit"]}

    # the check: every search of the window against the plain reference,
    # once the window has closed and memory has been read
    t_ref = time.perf_counter()
    per_search = check_searches(window.searches, v, reference, cell.traffic)
    ok, compared = judge(compared_numbers(per_search, cell.limits["far_gap"]), cell.limits)
    info(check={"reference_s": time.perf_counter() - t_ref,
                "k_opt_mismatches": sum(c["mismatch"] for c in per_search), "searches": [
        {"k_optimal": c["k_optimal"], "k_optimal_reference": c["k_optimal_reference"],
         "diff": c["diff"], "reference_scores": c["reference_scores"]} for c in per_search]})

    failed = sum(1 for s in window.searches if s.result.k_optimal is None)
    out = {
        "correct": bool(ok),
        "attempted": len(window.searches),
        "failed": failed,
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": memory_peak},
    }
    if trace and window.trace is not None:
        out["device"]["busy_s"] = window.trace["busy_s"]
        out["device"]["window_s"] = window.trace["window_s"]
        out["breakdown"] = {"device_ops": [list(x) for x in window.trace["device_ops"]],
                            "idle_gaps": [list(x) for x in window.trace["idle_gaps"]]}
    out["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})", file=log, flush=True)
    return out
