"""What the readers of the program's own spans share.

The program keeps a ring of its spans
(``deeplearning4j_tpu.profiler.telemetry``): the engine thread's account
(``engine.burst`` > ``engine.dispatch`` / ``engine.sync`` /
``engine.emit``, ``engine.admit`` > ``engine.prefill``,
``request.queue_wait``) and one record per compilation or load from the
compile cache (``jit.compile``, ``jit.cache_load``). The ring is module
state, so it is still there after the driver has freed the engine. It
is cut to the cell's window on the ring's own clock, ``perf_counter``:
from set-up's end for ``window_s`` seconds.

A burst belongs to the window its ``engine.sync`` ended in and an
admission to the one its ``engine.prefill`` ended in: the host's read of
the device's answer is what the clients' stamps, which close the
window, follow by microseconds to milliseconds, while the spans around
them close at about the same instant as the last stamp and could fall
on either side of it.

A program without these spans (an older commit) or with its telemetry
switched off has nothing to read: every function here then returns
None, and so does the reader.
"""


def ring():
    """The program's telemetry module where it can be read by time."""
    try:
        from deeplearning4j_tpu.profiler import telemetry
    except ImportError:
        return None
    if not hasattr(telemetry, "spans_between") or not telemetry.enabled():
        return None
    return telemetry


def window(run):
    """-> (t0, t1) of the measured window as ``perf_counter`` readings."""
    if run.window_s is None or run.e2e.get("setup_s") is None:
        return None
    t0 = run.t_start + run.e2e["setup_s"]
    return t0, t0 + run.window_s


def ended_in_window(run, name):
    """The ring's records of one name that ended inside the window, or
    None where there is no ring or no window."""
    tel, win = ring(), window(run)
    if tel is None or win is None:
        return None
    return tel.spans_between(win[0], win[1], name)


def _by_id(run, name):
    """Every record of one name since the process started, by id."""
    return {e["args"]["id"]: e for e in ring().spans_between(
        run.t_start, float("inf"), name)}


def bursts(run):
    """-> [(burst, sync, [dispatches])] of the window's decode bursts,
    or None where the ring holds none."""
    syncs = ended_in_window(run, "engine.sync")
    if not syncs:
        return None
    burst_of = _by_id(run, "engine.burst")
    under = {}
    for d in _by_id(run, "engine.dispatch").values():
        under.setdefault(d["args"].get("parent"), []).append(d)
    out = []
    for s in syncs:
        b = burst_of.get(s["args"].get("parent"))
        if b is not None:
            out.append((b, s, under.get(b["args"]["id"], [])))
    return out or None


def admissions(run):
    """-> [(admit, prefill)] of the window's admissions (may be empty),
    or None where there is no ring."""
    prefills = ended_in_window(run, "engine.prefill")
    if prefills is None:
        return None
    admit_of = _by_id(run, "engine.admit")
    return [(admit_of[p["args"]["parent"]], p) for p in prefills
            if p["args"].get("parent") in admit_of]


def median_ms(records):
    """Median duration in milliseconds (the ring keeps microseconds)."""
    durs = sorted(e["dur"] for e in records)
    n = len(durs)
    mid = durs[n // 2] if n % 2 else 0.5 * (durs[n // 2 - 1] + durs[n // 2])
    return mid / 1e3


JIT = ("jit.compile", "jit.cache_load")


def compiles_in_window(run):
    """``jit.compile`` + ``jit.cache_load`` records inside the window.
    None where the ring saw no such record before the window either:
    set-up compiles or loads every program, so a ring without one was
    not listening."""
    tel, win = ring(), window(run)
    if tel is None or win is None:
        return None
    if not any(e["name"] in JIT
               for e in tel.spans_between(run.t_start, win[1])):
        return None
    return sum(e["name"] in JIT for e in tel.spans_between(*win))
