"""One run of one cell: set up, drive the closed loop for ``--seconds``,
check the answers against the plain reference, print the result line.

    python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name under
``benchmarks/chip/``: the cell in ``BENCHMARK.json``; its configuration
in ``configs/``, whose ``history`` (``interactions`` where it names
none) names the module ``histories/<history>.py`` that generates its
event log; its traffic mix in ``traffic/<mix>.json``, whose ``kind``
names the module ``traffic/<kind>.py`` that turns it into requests;
each operation the mix names in ``operations/<op>.py``; and each
metric's reader in ``metrics/<name>.py``.

A history module exports ``history(cfg)``, the event columns in the
store's layout (``t, kind, src, dst, key, val``) sorted by time, with
``counts`` of what was made; and ``tails(cols, spans=())``, the tail
statistics of its own choosing that set the operand shapes, over the
whole history and in each ``(lo, hi)`` span under ``"windows"``.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from chipbench import tracing
from reference.analytics import Windows
from reference.replay import History

CHIP_DIR = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, prefix: str):
    """A file of the benchmark, loaded as a module by its path."""
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metrics_dir: Path, name: str):
    """The ``read`` function of a metric: ``metrics/<name>.py``, or for a
    name ``<base>.<qualifier>`` without a file of its own (one quantity
    split by the end-to-end metric it moves), ``metrics/<base>.py``."""
    path = metrics_dir / f"{name}.py"
    if not path.exists():
        path = metrics_dir / f"{name.split('.')[0]}.py"
    return load_module(path, "chipbench_metric_").read


def load_history(chip: Path, cfg: dict):
    """The configuration's history module, ``histories/<history>.py``
    (``interactions`` where the configuration names none)."""
    name = cfg.get("history", "interactions")
    path = chip / "histories" / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"unknown history {name!r}: there is no {path}")
    return load_module(path, "chipbench_history_")


def load_cell(root: Path, name: str) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    chip = root / "benchmarks" / "chip"
    history = load_history(chip, cfg)
    mix = json.loads((chip / "traffic" / f"{cell['traffic']}.json").read_text())
    kind = load_module(chip / "traffic" / f"{mix['kind']}.py",
                       "chipbench_kind_")
    ops = {op: load_module(chip / "operations" / f"{op}.py", "chipbench_op_")
           for op in mix["block"]}

    def listed(m):
        return "workloads" not in m or name in m["workloads"]

    return {"cell": cell, "cfg": cfg, "history": history, "mix": mix,
            "kind": kind, "ops": ops,
            "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
            "per_layer": [m for m in bench["per_layer"] if listed(m)],
            "metrics_dir": chip / "metrics"}


def start_device(chips: int, require_tpu: bool) -> dict:
    """Point JAX's compile cache at a fixed directory in the checkout and
    find the chips, or raise ``NoChip``."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHIP_DIR / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devs}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "devices": devs[:chips], "cache": cache}


def build(cfg: dict, history):
    from repro.core.events import EventLog
    from repro.taf import HistoricalGraphStore

    t = time.perf_counter()
    h = history.history(cfg)
    gen_s = time.perf_counter() - t
    published = {k: v for k, v in cfg["published"].items()
                 if not isinstance(v, dict)}
    log(f"generated: {json.dumps(h['counts'])} (published "
        f"{json.dumps(published)})")
    log(f"tails: {json.dumps(history.tails(h['cols']))} (configured "
        f"{json.dumps(cfg.get('assumed', {}).get('tails'))})")
    t = time.perf_counter()
    store = HistoricalGraphStore.build(EventLog(**h["cols"]), **cfg["store"])
    return h, store, gen_s, time.perf_counter() - t


def drive(store, spec: dict, cols: dict, time_range, seed: int,
          seconds: float, tracing_on: bool):
    """Closed loop, one client: the next request goes when the last has
    answered.  The window runs whole blocks of the mix until ``seconds``
    have passed, so every run does the mix's exact shares of work.  Keeps
    a reservoir of answers per operation, drawn from the seed, for the
    check."""
    import jax

    mix, kind, ops = spec["mix"], spec["kind"], spec["ops"]
    params, keep = mix.get("params", {}), mix["sample_per_op"]
    reqs = kind.requests(mix, time_range, seed, cols)
    rng = np.random.default_rng([seed, 2])
    block = len(kind.block(mix))
    records, samples, seen, failed = [], {}, {}, 0
    t_start = time.perf_counter()
    i = 0
    while time.perf_counter() - t_start < seconds or i % block:
        req = next(reqs)
        op = req["op"]
        span = (jax.profiler.TraceAnnotation(f"{tracing.SPAN_PREFIX}{op}:{i}")
                if tracing_on else contextlib.nullcontext())
        t0 = time.perf_counter()
        try:
            with span:
                got = ops[op].answer(ops[op].run(store, req, params))
        except Exception as e:  # a request that errs counts as failed
            failed += 1
            log(f"request {i} ({op}) failed: {type(e).__name__}: {e}")
            got = None
        t1 = time.perf_counter()
        records.append({"op": op, "i": i, "start": t0, "end": t1,
                        "latency_s": t1 - t0, "ok": got is not None,
                        "cost": (ops[op].cost(store)
                                 if hasattr(ops[op], "cost") else None)})
        if got is not None:
            k = seen.get(op, 0)
            seen[op] = k + 1
            slot = samples.setdefault(op, [])
            if len(slot) < keep:
                slot.append((req, got, i))
            else:
                j = int(rng.integers(k + 1))
                if j < keep:
                    slot[j] = (req, got, i)
        i += 1
    window_s = time.perf_counter() - t_start
    return records, samples, failed, window_s


def check(ref, spec: dict, samples: dict, control: bool = False, peak=None):
    """Compare every sampled answer with the reference.  With
    ``control``, an operation that has a control puts it in the
    program's place; it has to fail."""
    mix, ops = spec["mix"], spec["ops"]
    params = mix.get("params", {})
    worst, least = {}, {}
    for op in mix["block"]:
        if not samples.get(op):
            worst[f"{op}_unchecked"] = 1
    for op, rows in samples.items():
        mod = ops[op]
        for req, got, i in rows:
            want = mod.expect(ref, req, params)
            if control and hasattr(mod, "control"):
                got = mod.control(ref, req, params)
            name, val = mod.compare(req, got, want)
            worst[name] = max(worst.get(name, val), val)
            if peak is not None and hasattr(mod, "least"):
                least.setdefault(op, {})[i] = mod.least(ref, req, want,
                                                        params, peak)
    limits = {k: v for mod in ops.values() for k, v in mod.LIMITS.items()}
    checks = {k: {"value": v, "limit": limits.get(k, 0)}
              for k, v in sorted(worst.items())}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks, least


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def main(argv=None, root: Path = None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--control", type=int, default=0, choices=(0, 1),
                    help="put each operation's control in the program's "
                         "place (PageRank: the reference's in bfloat16); "
                         "must come out not correct")
    ap.add_argument("--records", default="",
                    help="also write each timed request (operation, start "
                         "and latency) as JSON lines to this file")
    args = ap.parse_args(argv)
    root = Path(root) if root else Path.cwd()
    spec = load_cell(root, args.workload)
    cfg, mix, kind, cell = spec["cfg"], spec["mix"], spec["kind"], spec["cell"]

    t_setup = time.perf_counter()
    try:
        dev = start_device(cell["chips"], require_tpu)
    except NoChip as e:
        log(f"error: {e}")
        return 2
    from chipbench import peaks
    from repro.taf import compile as tc

    # an unknown chip is an error; off the chip (tests) there is no peak
    peak = peaks.peaks(dev["kind"]) if require_tpu else None
    device_s = time.perf_counter() - t_setup
    h, store, gen_s, build_s = build(cfg, spec["history"])
    time_range = store.time_range()
    windows = kind.windows(mix, time_range)
    tails = spec["history"].tails(h["cols"], windows)
    log(f"window tails: {json.dumps(tails.get('windows', []))}")
    n_events = len(h["cols"]["t"])
    t = time.perf_counter()
    traces0 = tc.STATS["traces"]
    warm = kind.warm(mix, time_range, args.seed, h["cols"])
    for req in warm:
        t_req = time.perf_counter()
        spec["ops"][req["op"]].run(store, req, mix.get("params", {}))
        log(f"warm: {req['op']} {req.get('window', '')} "
            f"{time.perf_counter() - t_req:.3f} s")
    warm_s = time.perf_counter() - t
    setup_s = time.perf_counter() - t_setup
    log(f"setup: device_s={device_s:.3f} generate_s={gen_s:.3f} "
        f"build_s={build_s:.3f} warm_s={warm_s:.3f} warm_requests={len(warm)} "
        f"warm_traces={tc.STATS['traces'] - traces0} setup_s={setup_s:.3f} "
        f"compile_cache={dev['cache']}")

    stats0 = dict(tc.STATS)
    trace_dir = CHIP_DIR / ".trace" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        import jax

        jax.profiler.start_trace(str(trace_dir))
    records, samples, failed, window_s = drive(
        store, spec, h["cols"], time_range, args.seed, args.seconds,
        bool(args.trace))
    if args.records:
        with open(args.records, "w") as f:
            for r in records:
                f.write(json.dumps({"op": r["op"], "i": r["i"],
                                    "start_s": r["start"] - t_setup,
                                    "latency_s": r["latency_s"]}) + "\n")
    red = None
    if args.trace:
        jax.profiler.stop_trace()
        ex = tracing.extract(str(trace_dir))
        spans = ex["spans"]
        red = tracing.reduce(ex, (spans[0][0], spans[-1][1]) if spans
                             else (0.0, 0.0))
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats1 = dict(tc.STATS)
    mem = memory_peak(dev["devices"])
    stored = store.storage_report()["totals"]["encoded"]
    del store
    tc.clear_cache()

    t = time.perf_counter()
    ref = Windows(History(h["cols"]))
    correct, checks, least = check(ref, spec, samples, bool(args.control),
                                   peak)
    for op, per in least.items():
        for i, c in per.items():
            log(f"{op} {i} least work: {c['flops']} ops, {c['bytes']} bytes, "
                f"{c['least_s'] * 1e6:.3f} us, {c['bound']} bound")
    if failed:
        correct = False
    log(f"check: {sum(len(v) for v in samples.values())} sampled answers "
        f"against the reference in {time.perf_counter() - t:.3f} s")

    run = {"records": records, "window_s": window_s, "setup_s": setup_s,
           "stats_before": stats0, "stats_after": stats1, "trace": red,
           "stored_bytes": stored, "n_events": n_events, "least": least}
    wanted = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        v = reader(spec["metrics_dir"], m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": len(records),
           "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = red["breakdown"]
    out["checks"] = checks
    for k, c in checks.items():
        log(f"compared {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0
