"""One run of one cell: make the data, load it, serve a closed loop through
``KvBatchServer``, check every answer against the reference, report.

Everything that belongs to one cell is found by name: the cell's entry in
``BENCHMARK.json``, its configuration file, its traffic file under
``workloads/`` and one reader per per-layer metric under ``metrics/``.
Nothing here names a cell.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import re
import shutil
import tempfile
import time

import numpy as np

from . import reference, roofline, traffic
from . import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOAD_BATCH = 4096
READBACK_LOADED = 65536     # loaded records read back where epochs retire
_PCT = re.compile(r"^([a-z]+)_p(\d+)_ms$")
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_trace_duration")


# --------------------------------------------------------------------- spec
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    workload: dict
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str, reported=None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "workloads", name + ".json")) as f:
        workload = json.load(f)
    if workload["loop"] != "closed":
        raise ValueError(f"{name}: only closed loops are generated, not "
                         f"{workload['loop']!r}")
    if workload["config"] != entry["config"]:
        raise ValueError(f"{name}: traffic file names config "
                         f"{workload['config']!r}, BENCHMARK.json "
                         f"{entry['config']!r}")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name, entry["chips"], config, workload, e2e, per_layer)


def load_reader(metric: str, root: str = ROOT):
    """The module ``metrics/<metric>.py``: ``read(ctx)`` and, for a device
    form, ``WRAP`` (module, function) and ``shape(args, kwargs)``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -------------------------------------------------------------------- store
def store_config(config: dict):
    """The store's ``DbConfig``; the mappings ``wal``, ``index_wal`` and
    ``prune`` of the configuration's ``store`` become a ``WalConfig`` each
    and a ``PruneOptions``."""
    from repro.core.tidestore import (DbConfig, KeyspaceConfig, PruneOptions,
                                      WalConfig)
    ks = KeyspaceConfig("default", key_len=config["key_bytes"],
                        **config["keyspace"])
    store = dict(config["store"])
    for key, cls in (("wal", WalConfig), ("index_wal", WalConfig),
                     ("prune", PruneOptions)):
        if isinstance(store.get(key), dict):
            store[key] = cls(**store[key])
    return DbConfig(keyspaces=[ks], **store)


def load_store(path: str, config: dict, data: traffic.Dataset):
    """``put_many`` in batches of 4096, close (which flushes every cell's
    index), write the store's files back to disk, reopen cold.  The
    write-back is set-up's: left to the kernel, a gigabyte of dirty pages
    would go to disk during the window."""
    from repro.core.tidestore import TideDB
    cfg = store_config(config)
    db = TideDB(path, cfg)
    try:
        for i in range(0, len(data.keys), LOAD_BATCH):
            db.put_many(list(zip(data.keys[i:i + LOAD_BATCH],
                                 data.values[i:i + LOAD_BATCH])))
    finally:
        db.close()
    for d, _, files in os.walk(path):
        for f in files:
            fd = os.open(os.path.join(d, f), os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
    return TideDB(path, cfg)


# --------------------------------------------------------------------- loop
class ClosedLoop:
    """Keeps ``outstanding`` requests in the server's queue: before each
    ``step()`` it submits as many as the last step completed.  Op kinds,
    keys and values come pre-drawn from the sequence, so its work per
    request is one ``submit_*`` call and one clock read.

    With ``epoch_requests``, each top-up first sets the server's write
    epoch to 1 + completed // epoch_requests: a step serves one whole
    block, so request i writes with epoch 1 + i // epoch_requests.  A step
    that serves anything else counts in ``misaligned``."""

    def __init__(self, srv, seq: traffic.Sequence, outstanding: int,
                 epoch_requests=None):
        self.srv, self.seq, self.outstanding = srv, seq, outstanding
        self.epoch_requests = epoch_requests
        self.misaligned = 0
        self.op = seq.op.tolist()
        self.reqs: list = []
        self.t_submit: list = []
        self.ends: list = []            # (requests completed so far, t)
        self.topup_ends: list = []      # t at the end of each top-up
        self.completed = 0
        self.topup_s = 0.0

    def topup(self) -> None:
        t0 = time.perf_counter()
        srv, seq, op, reqs, ts = (self.srv, self.seq, self.op, self.reqs,
                                  self.t_submit)
        if self.epoch_requests:
            from repro.core.tidestore import WriteOptions
            srv.write_opts = WriteOptions(
                epoch=1 + self.completed // self.epoch_requests)
        submit = (srv.submit_get, srv.submit_exists)
        n = len(seq)
        for _ in range(self.outstanding - (len(reqs) - self.completed)):
            j = len(reqs) % n
            ts.append(time.perf_counter())
            if op[j] == reference.PUT:
                reqs.append(srv.submit_put(seq.key[j], seq.value[j]))
            else:
                reqs.append(submit[op[j]](seq.key[j]))
        t1 = time.perf_counter()
        self.topup_s += t1 - t0
        self.topup_ends.append(t1)

    def step(self) -> None:
        served = self.srv.step()
        if self.epoch_requests:
            self.misaligned += served != self.outstanding
        self.completed += served
        self.ends.append((self.completed, time.perf_counter()))
        # The served requests stay referenced for the check after the
        # window; frozen, they stay out of the collections that later
        # steps trigger, whose cost would otherwise grow with the window.
        gc.freeze()

    def latencies(self, first_step: int) -> tuple[np.ndarray, np.ndarray]:
        """(op kind, seconds from submit to the end of the step that
        completed it) of every request completed from ``first_step`` on."""
        c0 = self.ends[first_step - 1][0] if first_step else 0
        counts = np.diff([c0] + [c for c, _ in self.ends[first_step:]])
        t_done = np.repeat([t for _, t in self.ends[first_step:]], counts)
        idx = np.arange(c0, c0 + len(t_done))
        lat = t_done - np.asarray(self.t_submit[c0:c0 + len(t_done)])
        return self.seq.op[idx % len(self.seq)], lat


@contextlib.contextmanager
def _counting_compiles():
    from jax import monitoring
    count = [0]

    def on_event(event, duration, **_):
        if event in _COMPILE_EVENTS:
            count[0] += 1

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield count
    finally:
        monitoring.unregister_event_duration_listener(on_event)


# ------------------------------------------------------------- traced runs
class TracedEngine:
    """Forwards to the engine, with a host span around each batched call
    that ``KvBatchServer`` makes.  Used in traced runs only."""

    def __init__(self, db):
        self._db = db

    def __getattr__(self, name):
        return getattr(self._db, name)

    def _span(self, name, fn, *a, **k):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(name):
            return fn(*a, **k)

    def multi_get(self, *a, **k):
        return self._span("engine.multi_get", self._db.multi_get, *a, **k)

    def multi_exists(self, *a, **k):
        return self._span("engine.multi_exists", self._db.multi_exists,
                          *a, **k)

    def put_many(self, *a, **k):
        return self._span("engine.put_many", self._db.put_many, *a, **k)


@contextlib.contextmanager
def _wrapped_forms(readers: dict, calls: dict):
    """For each reader that names a device form's host entry (``WRAP``),
    replaces that entry for the run with one that records the call's
    shape in ``calls`` and opens a ``form.<function>`` span."""
    from jax.profiler import TraceAnnotation
    undo = []
    try:
        for r in readers.values():
            wrap = getattr(r, "WRAP", None)
            if wrap is None or wrap in calls:
                continue
            mod = importlib.import_module(wrap[0])
            orig = getattr(mod, wrap[1])
            calls[wrap] = []

            def wrapped(*a, _orig=orig, _rec=calls[wrap], _shape=r.shape,
                        _name="form." + wrap[1], **k):
                _rec.append(_shape(a, k))
                with TraceAnnotation(_name):
                    return _orig(*a, **k)

            setattr(mod, wrap[1], wrapped)
            undo.append((mod, wrap[1], orig))
        yield calls
    finally:
        for mod, fn, orig in undo:
            setattr(mod, fn, orig)


def _numeric(d: dict) -> dict:
    return {k: v for k, v in d.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in _numeric(b) if k in a}


# ---------------------------------------------------------------------- run
def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_process: float, log=print, engine_wrap=None) -> dict:
    """Runs ``cell`` once and returns the result line's object.
    ``engine_wrap`` puts another engine in the store's place behind the
    server (the control of the correctness check)."""
    from repro import compile_cache
    from repro.serving.engine import KvBatchServer

    compile_cache.enable(ROOT)
    cfg, wl = cell.config, cell.workload
    data = traffic.make_dataset(cfg, seed)
    seq = traffic.make_sequence(cfg, wl, data, seed)
    log(f"bench: {cell.name} seed={seed} records={cfg['records']} "
        f"sequence={len(seq)} outstanding={wl['outstanding']}")
    # The records and the request sequence are the client's; frozen, they
    # stay out of every garbage collection the served path triggers.
    gc.collect()
    gc.freeze()
    readers = ({m["name"]: load_reader(m["name"]) for m in cell.per_layer}
               if traced else {})
    path = tempfile.mkdtemp(prefix="tidebench-")
    try:
        t = time.perf_counter()
        db = load_store(path, cfg, data)
        log(f"bench: loaded and reopened in {time.perf_counter() - t:.3f} s")
        served = engine_wrap(db, data) if engine_wrap else db
        if traced:
            served = TracedEngine(served)
        srv = KvBatchServer(served, max_batch=wl["outstanding"],
                            prune_opts=store_config(cfg).prune)
        loop = ClosedLoop(srv, seq, wl["outstanding"],
                          traffic.epoch_requests(cfg, wl))
        try:
            result = _serve(cell, loop, db, seconds, traced, readers,
                            t_process, log, path)
            if srv.prune_opts is not None:
                _finish_reclamation(srv, db, log)
        finally:
            srv.close()
            db.close()
        checks = _check(cfg, seq, data, loop, path, log, seed)
    finally:
        shutil.rmtree(path, ignore_errors=True)
        gc.unfreeze()
    result["correct"] = all(v <= lim for v, lim in checks.values())
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}={v} limit={lim}", err=True)
    return result


def _warm_up(cell: Cell, loop: ClosedLoop, db, log) -> None:
    """Serves the cell's own traffic, ``warmup.requests`` of it: a fixed
    amount from the seed, so set-up does the same work in every run.  The
    line it logs says how many values the value cache took in against its
    capacity in values."""
    cfg = cell.config
    capacity = cfg["store"]["cache_bytes"] // (cfg["value_bytes"]
                                               + cfg["key_bytes"] + 2)
    miss0 = db.stats()["cache_misses"]
    t = time.perf_counter()
    while loop.completed < cell.workload["warmup"]["requests"]:
        loop.topup()
        loop.step()
    log(f"bench: warm-up served {loop.completed} requests in "
        f"{len(loop.ends)} steps, {time.perf_counter() - t:.3f} s; value "
        f"cache took in {db.stats()['cache_misses'] - miss0} values "
        f"(capacity {capacity})")


def _serve(cell, loop, db, seconds, traced, readers, t_process, log,
           path) -> dict:
    import jax
    cfg = cell.config
    _warm_up(cell, loop, db, log)
    srv = loop.srv
    s0, d0 = srv.stats(), db.stats()
    first = len(loop.ends)
    calls: dict = {}
    tdir = os.path.join(path, "trace")
    span = contextlib.nullcontext
    with contextlib.ExitStack() as stack:
        if traced:
            from jax.profiler import ProfileOptions, TraceAnnotation
            stack.enter_context(_wrapped_forms(readers, calls))
            opts = ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            span = TraceAnnotation
        compiles = stack.enter_context(_counting_compiles())
        loop.topup_s = 0.0
        for rec in calls.values():
            rec.clear()
        t_w0 = time.perf_counter()
        setup_s = t_w0 - t_process
        with span("bench.window"):
            while True:
                with span("bench.topup"):
                    loop.topup()
                with span("serving.step"):
                    loop.step()
                if loop.ends[-1][1] - t_w0 >= seconds:
                    break
        t_w1 = loop.ends[-1][1]
        if traced:
            jax.profiler.stop_trace()
    s1, d1 = srv.stats(), db.stats()
    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    ops, lat = loop.latencies(first)
    window_s = t_w1 - t_w0
    steps = len(loop.ends) - first
    log(f"bench: window {window_s:.6f} s, {steps} steps, {len(lat)} "
        f"requests; generator {100 * loop.topup_s / window_s:.3f} % of loop "
        f"time; compiles inside the window: {compiles[0]}")
    ends = np.array([t_w0] + [t for _, t in loop.ends[first:]])
    topped = np.array(loop.topup_ends[first:])
    slow = np.argsort(np.diff(ends))[::-1][:5]
    log("bench: slowest steps (s, of which top-up s, at s into the window): "
        + ", ".join(f"{ends[i + 1] - ends[i]:.3f} ({topped[i] - ends[i]:.3f})"
                    f" at {ends[i] - t_w0:.1f}" for i in slow))
    done, lat_ms = {}, {}
    for i, kind in enumerate(traffic.OPS):
        sel = lat_ms[kind] = lat[ops == i] * 1e3
        done[kind] = int(sel.size)
        if sel.size:
            log(f"bench: {kind} n={sel.size} p50={np.median(sel):.6f} ms "
                f"p99={np.percentile(sel, 99):.6f} ms "
                f"max={sel.max():.6f} ms")
    c0 = loop.ends[first - 1][0] if first else 0
    failed = sum(r.error is not None for r in loop.reqs[c0:loop.completed])
    dev = devices[0]
    result = {"correct": None, "attempted": int(len(lat)), "failed": failed,
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": peak}}
    if not traced:
        e2e = {"setup_s": setup_s, "ops_per_s": len(lat) / window_s}
        for m in cell.end_to_end:
            name = m["name"]
            pct = _PCT.match(name)
            if pct:
                sel = lat[ops == traffic.OPS.index(pct.group(1))]
                value = (float(np.percentile(sel, int(pct.group(2)))) * 1e3
                         if sel.size else None)
            else:
                value = e2e[name]
            if value is not None:
                result["metrics"][name] = {"value": value, "unit": m["unit"]}
        return result
    xplane = [os.path.join(d, f) for d, _, fs in os.walk(tdir) for f in fs
              if f.endswith(".xplane.pb")]
    spans = {"bench.window", "bench.topup", "serving.step",
             "engine.multi_get", "engine.multi_exists", "engine.put_many"}
    spans |= {"form." + w[1] for w in calls}
    red = trace_mod.reduce(trace_mod.load(xplane[0], spans))
    shutil.rmtree(tdir, ignore_errors=True)
    ctx = {"db": _delta(d0, d1), "srv": _delta(s0, s1), "done": done,
           "latency_ms": lat_ms, "trace": red, "calls": calls, "config": cfg,
           "peak": roofline.peaks(dev.device_kind)}
    for m in cell.per_layer:
        value = readers[m["name"]].read(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["device"]["busy_s"] = red["busy_s"]
    result["device"]["window_s"] = red["window_s"]
    result["breakdown"] = {"device_ops": red["device_ops"],
                           "idle_gaps": red["idle_gaps"]}
    log(f"bench: traced window {red['window_s']:.6f} s, device busy "
        f"{red['busy_s']:.6f} s; programs " + ", ".join(
            f"{k}: {v['runs']} runs {v['seconds']:.6f} s"
            for k, v in sorted(red["modules"].items())))
    return result


def _finish_reclamation(srv, db, log, limit: int = 100_000) -> None:
    """Serves idle steps, which run the server's reclamation slice, until
    one starts with no relocation pass in flight: that slice drops every
    segment below the final floor (``reference`` module doc)."""
    rel = getattr(db, "relocator", None)
    for steps in range(1, limit + 1):
        scanning = rel is not None and rel.scanning
        srv.step()
        if not scanning:
            break
    log(f"bench: {steps} idle steps after the window finished reclamation")


def _check(cfg, seq, data, loop, path, log, seed) -> dict:
    """The comparison with the reference, after the window and after the
    store is closed: every answer served (warm-up and window), then every
    key written, read back from the store reopened cold.  Where epochs
    retire writes, the read-back takes the keys still retained and a
    sample of the loaded records, and ``expired_present`` counts the keys
    that must be gone and are not."""
    from repro.core.tidestore import TideDB
    t = time.perf_counter()
    oracle = reference.DictOracle(data.keys, data.values)
    counts = oracle.replay(seq, loop.reqs, loop.epoch_requests)
    errors = sum(r.error is not None for r in loop.reqs)
    checks = {"wrong_answers": (counts["wrong_answers"], 0),
              "unanswered": (counts["unanswered"], 0),
              "errors": (errors, 0)}
    n = len(seq)
    written = {seq.key[i % n] for i in range(len(loop.reqs))
               if seq.op[i % n] == reference.PUT}
    loaded, gone = [], None
    if loop.epoch_requests:
        checks["epoch_misaligned"] = (loop.misaligned, 0)
    db_cfg = store_config(cfg)
    retain = db_cfg.prune.retain_epochs if db_cfg.prune else None
    if loop.epoch_requests and retain is not None:
        e = loop.epoch_requests
        puts = int((seq.op[:e] == reference.PUT).sum())
        seg_records = db_cfg.wal.segment_size // (cfg["key_bytes"]
                                                  + cfg["value_bytes"])
        floor, written, gone = oracle.retention(retain, seg_records,
                                                max(puts, 1))
        rng = traffic.rng_for(seed, 4)
        take = min(len(data.keys), READBACK_LOADED)
        loaded = [data.keys[i] for i in
                  rng.choice(len(data.keys), take, replace=False).tolist()]
        log(f"bench: final floor {floor}; {len(written)} keys retained, "
            f"{len(gone)} must be gone")
    if written or loaded or gone is not None:
        db = TideDB(path, db_cfg)
        try:
            checks["readback_wrong"] = (
                oracle.read_back(db, list(written) + loaded), 0)
            if gone is not None:
                checks["expired_present"] = (
                    oracle.count_present(db, gone), 0)
        finally:
            db.close()
    log(f"bench: compared {len(loop.reqs)} answers and read back "
        f"{len(written)} written keys and {len(loaded)} loaded records in "
        f"{time.perf_counter() - t:.3f} s")
    return checks
