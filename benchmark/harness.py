"""What every cell's run shares: finding files by the names in
``BENCHMARK.json``, the look for the chip, set-up phases, the traced
sub-window, the device's memory peak and the result line.

Nothing here knows a configuration, a traffic mix, a driver or a metric
by name. A cell names its configuration and traffic; the traffic file
names its driver; the configuration names its family, which finds the
model arithmetic, the plain reference and the program's model; a per-layer metric's reader is
the file ``readers/<metric name>.py``. A later PR adds files and entries
and edits nothing that is here.
"""

import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


class Refused(Exception):
    """The run cannot be measured; ``run.py`` exits non-zero, no result."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import one file by path under a name made from the path, so that
    ``drivers/x.py`` and ``readers/x.py`` never meet in ``sys.modules``."""
    if not os.path.isfile(path):
        raise Refused(f"no such file: {path}")
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def merge(base, over):
    """``over`` laid onto ``base``, dict by dict (rehearsal sizes)."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    def __init__(self, root, workload, rehearse=None):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                          f"(have: {', '.join(sorted(cells))})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(root, conf["file"]))
        self.dir = os.path.dirname(os.path.dirname(
            os.path.join(root, conf["file"])))
        self.traffic = load_json(os.path.join(
            self.dir, "traffic", self.entry["traffic"] + ".json"))
        if rehearse:
            over = load_json(rehearse)
            self.config = merge(self.config, over.get("configs", {}).get(
                self.entry["config"], {}))
            self.traffic = merge(self.traffic, over.get("traffic", {}).get(
                self.entry["traffic"], {}))
        family = self.config["family"]
        self.flops = load_module(os.path.join(self.dir, "flops", family + ".py"))
        self.reference = load_module(
            os.path.join(self.dir, "reference", family + ".py"))
        self.program = load_module(
            os.path.join(self.dir, "program", family + ".py"))
        self.driver = load_module(os.path.join(
            self.dir, "drivers", self.traffic["driver"] + ".py"))
        self.generator = load_module(os.path.join(self.dir, "traffic_gen.py"))
        self.reducer = load_module(os.path.join(self.dir, "trace_reduce.py"))
        self.peaks_table = load_json(os.path.join(self.dir, "peaks.json"))

    def _mine(self, metric):
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self._mine(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self._mine(m)]

    def reader(self, metric_name):
        return load_module(os.path.join(
            self.dir, "readers", metric_name + ".py"))


def look_for_chip(cell, rehearse):
    """The devices as JAX reports them, or ``Refused``: no accelerator,
    a kind with no row in ``peaks.json``, or fewer chips than the cell
    asks for. A rehearsal (the benchmark's own ``--rehearse``) skips the
    look and names the platform it ran on in every line."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearse:
        # no peak is this device's: the readers of shares find nothing
        cell.peaks = cell.peaks_table.get(info["kind"])
        return info, devs[:cell.chips]
    if info["platform"] != "tpu":
        raise Refused(f"no accelerator: JAX reports platform "
                      f"{info['platform']!r} ({info['kind']} x{info['count']}); "
                      "this benchmark measures only on a TPU")
    if info["kind"] not in cell.peaks_table:
        raise Refused(f"device kind {info['kind']!r} has no row in "
                      "benchmark/peaks.json")
    if info["count"] < cell.chips:
        raise Refused(f"the cell needs {cell.chips} chips, JAX reports "
                      f"{info['count']}")
    cell.peaks = cell.peaks_table[info["kind"]]
    return info, devs[:cell.chips]


class Run:
    """The context a driver works in and the readers read."""

    def __init__(self, cell, args, t_process_start, device, devices):
        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.traced = bool(int(args.trace))
        self.rehearsal = bool(args.rehearse)
        self.device = dict(device)
        self.devices = devices
        self.t_start = t_process_start
        self.phases = []          # [(name, seconds)] of set-up
        self._phase_t = t_process_start
        self.out_dir = os.path.join(cell.root, "benchmark_out")
        # filled by the driver
        self.window_s = None
        self.e2e = {}             # end-to-end values by metric name
        self.counters = {}        # program counters, window deltas
        self.samples = {}         # the driver's own per-request records
        self.trace = None         # trace_reduce.reduce(...) of the sub-window
        self._trace_dir = self._trace_span = None
        self.checks = {}          # name -> {"value", "limit"}
        self.attempted = 0
        self.failed = 0

    # ---------------------------------------------------------- talking
    def say(self, text):
        d = self.device
        print(f"[{d['platform']} {d['kind']} x{d['count']}] {text}",
              flush=True)

    def phase(self, name):
        """Close the set-up phase that just ended under ``name``."""
        now = time.perf_counter()
        self.phases.append((name, now - self._phase_t))
        self._phase_t = now

    def setup_done(self):
        """Set-up ends and the window starts NOW."""
        now = time.perf_counter()
        self.e2e["setup_s"] = now - self.t_start
        self.say("setup phases (s): " + ", ".join(
            f"{n}={s:.2f}" for n, s in self.phases)
            + f"; setup_s={self.e2e['setup_s']:.2f}")
        return now

    def annotate(self, name):
        """A host span in the profiler's own trace (``bench:<name>``)."""
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)

    # ---------------------------------------------------------- tracing
    def trace_if_due(self, now, t_end):
        """In a traced run, start the profiler once ``now`` is within the
        mix's ``trace.seconds`` (3 by default) of the window's end:
        device operations and the benchmark's own annotations, no Python
        tracer. True when it started on this call."""
        if not self.traced or self._trace_dir is not None \
                or now < self.trace_next_due(t_end):
            return False
        import jax

        d = os.path.join(self.out_dir, "trace")
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        self._trace_dir = d
        self._trace_span = jax.profiler.TraceAnnotation(
            self.cell.reducer.WINDOW_NAME)
        self._trace_span.__enter__()
        return True

    def trace_next_due(self, t_end):
        """When the trace is due (``t_end`` itself where none is), for a
        driver that sleeps."""
        if not self.traced or self._trace_dir is not None:
            return t_end
        return t_end - float(self.cell.traffic.get("trace", {}).get(
            "seconds", 3.0))

    def trace_stop(self):
        """Stop the profiler (which takes seconds: call it once the
        window has closed). Nothing to do where none was started."""
        if self._trace_span is None:
            return
        import jax

        self._trace_span.__exit__(None, None, None)
        self._trace_span = None
        jax.profiler.stop_trace()

    def reduce_trace(self):
        """Reduce the stopped trace to ``self.trace`` and delete its
        files (they lie under ``benchmark_out/`` in the checkout)."""
        if self._trace_dir is None:
            return
        tr = self.cell.reducer
        raw = tr.read_xplane(tr.find_xplane(self._trace_dir))
        self.say("trace lines: " + json.dumps(raw["lines"]))
        try:
            self.trace = tr.reduce(raw)
        except ValueError:
            if not self.rehearsal:      # a traced run with no device work
                raise
            self.say("rehearsal: the trace holds no device operation")
        if os.environ.get("BENCHMARK_KEEP_TRACE"):
            keep = {"device": {k: v[:4000] for k, v in raw["device"].items()},
                    "host": raw["host"][:4000], "kinds": raw["kinds"]}
            with open(os.path.join(self.out_dir, "trace_events.json"), "w") as f:
                json.dump(keep, f)
        shutil.rmtree(self._trace_dir, ignore_errors=True)

    def memory_peak(self):
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.device["memory_peak_bytes"] = peak
        return peak

    # ----------------------------------------------------------- result
    def check(self, name, value, limit):
        self.checks[name] = {"value": value, "limit": limit}

    def correct(self):
        return bool(self.checks) and all(
            c["value"] is not None and c["value"] <= c["limit"]
            for c in self.checks.values())

    def result(self):
        cell = self.cell
        metrics = {}
        if self.traced:
            for m in cell.per_layer():
                value = cell.reader(m["name"]).read(self)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in cell.end_to_end():
                if self.e2e.get(m["name"]) is not None:
                    metrics[m["name"]] = {"value": self.e2e[m["name"]],
                                          "unit": m["unit"]}
        if self.rehearsal:
            # a rehearsal measures nothing: names and units, no numbers
            metrics = {k: {"value": None, "unit": v["unit"]}
                       for k, v in metrics.items()}
        line = {"correct": self.correct(), "attempted": self.attempted,
                "failed": self.failed, "metrics": metrics,
                "device": self.device}
        if self.traced and self.trace is not None:
            line["device"]["busy_s"] = self.trace["busy_s"]
            line["device"]["window_s"] = self.trace["window_s"]
            line["breakdown"] = {"device_ops": self.trace["device_ops"],
                                 "idle_gaps": self.trace["idle_gaps"]}
        line["checks"] = self.checks
        return line
