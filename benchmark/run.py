#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that fails at once without a TPU, makes its data from the
seed, boots the in-process cluster (one coordinator, the configuration's
workers, shipped ExecConfig), warms up the cell's own statements, measures
for `--seconds`, then holds every statement of the window to the plain
reference. Earlier lines of stdout are one JSON object per phase; the last
line is the result. `--rehearse-sf <f>` (off the TPU only) walks the same
phases at a tiny scale on whatever backend there is; it never says
`correct: true` and exits 1.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "config": config,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def _load(kind: str, name: str, attr: str):
    """`<kind>/<name>.py` of this directory, found by name: its `attr`."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def load_reader(kind: str, name: str):
    """A metric's reader: `read(run)` -> number, or None (nothing to read)."""
    return _load(kind, name, "read")


def load_reference(qid: str):
    """A query's plain reference: `answer(data, params, arith="exact")`."""
    return _load("reference", qid, "answer")


def load_traffic(spec: dict):
    """(mix, {query id: query file}, comparison limits) of a cell."""
    from benchmark import traffic

    mix = traffic.load_mix(spec["cell"]["traffic"])
    queries = {q["id"]: traffic.load_query(q["id"]) for q in mix["queries"]}
    limits = {"wrong_statements": 0, "double_rel_err_max": 0.0}
    for q in queries.values():
        for name, lim in q["limits"].items():
            limits[name] = max(limits.get(name, 0), lim)
    return mix, queries, limits


class CacheEvents:
    """Counts jax's persistent-compilation-cache events in this process."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def explain_engines(url: str, sql: str, session) -> dict:
    """Breaker engines as EXPLAIN ANALYZE names them over the protocol."""
    from presto_tpu import client

    _, rows = client.execute(url, "explain analyze " + sql, session)
    engines, why = {}, []
    for (line,) in rows:
        parts = line.split()
        if not parts:
            continue
        if "[engine=" in line:
            why.append(line.strip())
        elif parts[0].startswith("breaker.engine_") and "rows=" in line:
            n = int(line.split("rows=")[1].split()[0].replace(",", ""))
            eng = parts[0][len("breaker.engine_"):]
            engines[eng] = engines.get(eng, 0) + n
    return {"engines": engines, "engine_why": why}


def run_statement(url, sql, session, log, index, qid, pkey, keep_rows=True):
    """One statement through the program's client; never raises."""
    from benchmark.spans import TimedClient

    rec = {"index": index, "query": qid, "params_key": pkey, "columns": None,
           "rows": None, "error": None, "polls": 0, "query_id": None,
           "progress_uri": None}
    rec["t0"] = time.perf_counter()
    try:
        st = TimedClient(url, sql, session, log, index)
        rows = list(st.rows())
        rec["columns"], rec["polls"] = st.columns, st.polls
        rec["query_id"], rec["progress_uri"] = st.query_id, st.progress_uri
        rec["rows"] = rows if keep_rows else len(rows)
        rec["client"] = st
    except Exception as e:  # a failed statement is a result, not a crash
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["t1"] = time.perf_counter()
    return rec


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             device, sf_override=None) -> dict:
    """Everything after the look for a chip. Returns the result object."""
    import jax

    import presto_tpu  # noqa: F401  (x64 on, compile cache decided)
    from presto_tpu import client
    from presto_tpu.exec import ExecConfig, programs
    from presto_tpu.server.__main__ import build_catalog
    from presto_tpu.server.coordinator import DistributedRunner

    from benchmark import compare, traffic, trace_reduce
    from benchmark.spans import SpanLog

    events = CacheEvents()
    t_import = time.perf_counter()
    spec = load_cell(workload)
    config = spec["config"]
    sf = float(config["scale_factor"] if sf_override is None else sf_override)
    bdata = importlib.import_module("benchmark." + config["data_module"])
    mix, queries, limits = load_traffic(spec)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks_table = json.load(f)
    if device.platform == "tpu" and device.device_kind not in peaks_table:
        raise SystemExit(f"run.py: no peaks for device kind "
                         f"{device.device_kind!r} in benchmark/peaks.json")
    peaks = peaks_table.get(device.device_kind)

    # -- data: once, for catalog and reference alike
    tables = sorted({t for q in queries.values() for t in q["tables"]})
    data = bdata.generate(sf, seed, tables)
    t_data = time.perf_counter()
    catalog = build_catalog([config["catalog"].format(scale_factor=f"{sf:g}")])
    bdata.install(catalog, sf, seed, data)
    rows_in = {qid: bdata.scanned_rows(q, data) for qid, q in queries.items()}
    bytes_in = {qid: bdata.referenced_bytes(q, data) for qid, q in queries.items()}
    emit({"phase": "data", "sf": sf, "seed": seed, "tables": tables,
          "import_s": t_import - T_START, "generate_s": t_data - t_import,
          "install_s": time.perf_counter() - t_data,
          "scanned_rows": rows_in, "referenced_bytes": bytes_in})

    # the shipped ExecConfig() and an empty session, unless the
    # configuration's file says otherwise
    runner = DistributedRunner(catalog, n_workers=int(config["workers"]),
                               config=ExecConfig(**config["exec_config"]))
    profile_dir = os.path.join(HERE, ".cache", "trace")
    try:
        url = runner.coordinator.url
        session = client.ClientSession(user="benchmark")
        session.properties.update(config["session_properties"])
        if trace:  # the traced run alone asks for the lifecycle timeline
            session.properties["lifecycle"] = "on"
        log = SpanLog(annotate=trace)

        # -- warm-up: the cell's own texts, compile or cache load, first upload
        snap0 = programs.snapshot()
        warm, first_walls = [], []
        texts = traffic.warmup_texts(mix, queries)
        for n_text, (qid, sql) in enumerate(texts):
            t_w, i = time.perf_counter(), 0
            while i < int(mix.get("warmup", 2)) or \
                    time.perf_counter() - t_w < float(mix.get("warmup_seconds", 0)):
                rec = run_statement(url, sql, session, SpanLog(), -1, qid, "",
                                    keep_rows=False)
                if rec["error"]:
                    raise RuntimeError(f"warm-up of {qid} failed: {rec['error']}")
                if i == 0:
                    first_walls.append(rec["t1"] - rec["t0"])
                if i < 3 and n_text < 3:  # the earlier lines stay short
                    warm.append({"query": qid, "n": i,
                                 "wall_s": rec["t1"] - rec["t0"]})
                i += 1
            if n_text < 3:
                warm.append({"query": qid, "statements": i,
                             "seconds": time.perf_counter() - t_w})
        snap1 = programs.snapshot()
        setup_counters = {
            "compiles": snap1["compiles"] - snap0["compiles"],
            "trace_wall_s": snap1["trace_wall_s"] - snap0["trace_wall_s"],
            "entries": snap1["entries"],
            "texts": len(texts), "first_walls_s": first_walls,
            "persistent_cache_hits": events.hits,
            "persistent_cache_misses": events.misses}
        early = {"phase": "warmup", "statements": warm, **setup_counters,
                 "hbm": device.memory_stats() or {},
                 "compile_cache_dir": jax.config.jax_compilation_cache_dir}
        if trace:
            _, sql = texts[0]
            early.update(explain_engines(url, sql, client.ClientSession(
                user="benchmark")))
        emit(early)

        # -- the window
        window_ann = None
        if trace:
            shutil.rmtree(profile_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
            window_ann = jax.profiler.TraceAnnotation("bench:traced_window")
        statements = []
        traced = {"open": trace, "t0": None, "t1": None, "statements": 0,
                  "stop_s": 0.0}
        hits0, miss0 = events.hits, events.misses
        snap_w = programs.snapshot()
        setup_s = time.perf_counter() - T_START
        w0 = time.perf_counter()
        deadline = w0 + seconds
        if trace:
            window_ann.__enter__()
            traced["t0"] = time.perf_counter()

        def close_trace():
            window_ann.__exit__(None, None, None)
            traced["t1"] = time.perf_counter()
            jax.profiler.stop_trace()
            traced["stop_s"] = time.perf_counter() - traced["t1"]
            traced["open"] = False

        last_end = None
        for qid, params, sql in traffic.stream(mix, queries, seed):
            if time.perf_counter() >= deadline:
                break
            index = len(statements)
            if last_end is not None:
                log.spans.append(("between_statements", index, last_end,
                                  time.perf_counter()))
            rec = run_statement(url, sql, session, log, index, qid,
                                traffic.params_key(params))
            rec["params"] = params
            if trace and rec.get("client") is not None:
                with log.span("progress_get", index):
                    rec["progress"] = rec["client"].progress()
            rec.pop("client", None)
            statements.append(rec)
            last_end = time.perf_counter()
            if traced["open"]:
                traced["statements"] += 1
                if (last_end - traced["t0"] >= float(mix["traced_seconds"])
                        and traced["statements"]
                        >= int(mix["traced_min_statements"])) \
                        or last_end >= deadline:
                    close_trace()
                    last_end = time.perf_counter()
        w1 = time.perf_counter()
        if traced["open"]:  # the window held no statement
            close_trace()
        snap2 = programs.snapshot()
        mem = device.memory_stats() or {}
        window_counters = {
            "compiles": snap2["compiles"] - snap_w["compiles"],
            "persistent_cache_hits": events.hits - hits0,
            "persistent_cache_misses": events.misses - miss0}
    finally:
        runner.close()
    del runner, catalog

    # -- after the window: the reference, once per distinct text, and the
    # comparison of every statement the window ran
    t_ref = time.perf_counter()
    references = {}
    for st in statements:
        key = (st["query"], st["params_key"])
        if key not in references and not st["error"]:
            references[key] = (
                load_reference(st["query"])(data, st["params"]),
                queries[st["query"]]["result_columns"])
    verdict = compare.judge(statements, references, limits)
    reference_s = time.perf_counter() - t_ref

    reduced = None
    if trace:
        found = sorted(glob.glob(os.path.join(
            profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if found:
            t_tr = time.perf_counter()
            reduced = trace_reduce.reduce(trace_reduce.load_xplane(found[-1]))
            emit({"phase": "trace", "xplane_bytes": os.path.getsize(found[-1]),
                  "read_s": time.perf_counter() - t_tr,
                  "profiler_stop_s": traced["stop_s"],
                  "reduced": {k: v for k, v in (reduced or {}).items()
                              if k not in ("device_ops", "idle_gaps")}})
        shutil.rmtree(profile_dir, ignore_errors=True)

    done = [s for s in statements if not s["error"]]
    run = {
        "workload": workload, "seed": seed, "trace": trace,
        "window_s": w1 - w0, "setup_s": setup_s,
        "statements": statements, "completed": done,
        "rows_in": rows_in, "bytes_in": bytes_in,
        "spans": log.spans, "setup_counters": setup_counters,
        "window_counters": window_counters,
        "traced": traced, "device_trace": reduced, "peaks": peaks,
        "memory_stats": mem,
    }
    metrics = {}
    kind, wanted = ("layer_metrics", spec["per_layer"]) if trace else \
        ("end_to_end", spec["end_to_end"])
    for m in wanted:
        value = load_reader(kind, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result = {"correct": verdict["correct"], "attempted": len(statements),
              "failed": len(statements) - len(done), "metrics": metrics,
              "device": dev}
    if trace and reduced:
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    walls = [s["t1"] - s["t0"] for s in done]
    quarters = [sum(walls[i * len(walls) // 4:(i + 1) * len(walls) // 4])
                / max(1, (i + 1) * len(walls) // 4 - i * len(walls) // 4)
                for i in range(4)] if len(walls) >= 4 else []
    emit({"phase": "check", "reference_s": reference_s,
          "mean_wall_by_quarter_s": quarters,
          "statements": len(statements), "window_s": w1 - w0,
          "first_difference": verdict["first_difference"],
          **window_counters})
    result["compared"] = verdict["compared"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-sf", type=float, default=None,
                    help="off the TPU only: walk the phases at this scale "
                         "factor; never correct, exit 1")
    args = ap.parse_args(argv)

    try:
        import jax

        import presto_tpu  # noqa: F401
    except ImportError as e:
        print(f"run.py: the system under test is not here: {e}", file=sys.stderr)
        return 2
    # a wrong name fails before the chip is touched
    chips = load_cell(args.workload)["cell"]["chips"]
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.rehearse_sf is None:
        print(f"run.py: no TPU - jax found platform {dev.platform!r}; "
              "nothing was run", file=sys.stderr)
        return 1
    if on_tpu and args.rehearse_sf is not None:
        print("run.py: --rehearse-sf is for rehearsals off the TPU",
              file=sys.stderr)
        return 1
    if on_tpu and len(devices) < chips:
        print(f"run.py: the cell asks for {chips} chip(s), jax found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), dev, sf_override=args.rehearse_sf)
    except Exception:
        traceback.print_exc()
        return 3
    if not on_tpu:
        result["correct"] = False  # a rehearsal is never a measurement
        result["rehearsal"] = True
        result["compared"] = result.pop("compared")  # stays last
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    # on the chip the result line carries the verdict; a rehearsal exits 1
    return 0 if on_tpu else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    sys.exit(code)
