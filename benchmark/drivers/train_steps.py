"""Training steps dispatched back to back through the one compiled
``CausalLM.make_train_step(Adam)`` and its state. Set-up builds that
object, drives it from the seed through its first steps (which the
reference follows afterwards) and hands the same object to the window.
"""

import collections
import gc
import time

import numpy as np

IN_FLIGHT = 2      # steps dispatched before the host waits for the oldest


def run(run):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.learning.updaters import Adam

    cell = run.cell
    cfg, mix, ref = cell.config, cell.traffic, cell.reference
    dep = cfg["deployment"]
    tr_cfg = dep["trainer"]
    rows, T = int(dep["rows"]), int(dep["positions"])
    model = cell.program.causal_lm(cfg)
    updater = Adam(learning_rate=float(tr_cfg["learning_rate"]),
                   beta1=float(tr_cfg["beta1"]), beta2=float(tr_cfg["beta2"]),
                   epsilon=float(tr_cfg["epsilon"]))
    state = {"params": ref.make_params(cfg, run.seed, layout="program")}
    state["opt"] = updater.init_state(state["params"])
    jax.block_until_ready(state)
    step = model.make_train_step(updater)
    key = jax.random.key(0)            # dropout is 0: the key is not drawn from
    batches = cell.generator.training_batches(rows, T, int(cfg["vocab_size"]), run.seed)
    run.phase("weights")
    count = [0]

    def feed():
        with run.annotate("make_batch"):
            ids = next(batches)
        with run.annotate("device_put"):
            return ids, jax.device_put(ids)

    def call(dev_ids):
        """The window's own call: one step on the one state."""
        with run.annotate("step_dispatch"):
            state["params"], state["opt"], loss = step(
                state["params"], state["opt"], jnp.asarray(count[0]),
                dev_ids, key)
        count[0] += 1
        return loss

    # ---- the first steps: the timed object, followed by the reference
    first, prog = [], {"losses": []}
    n_check = int(mix["check_steps"])
    t_steps = []
    for i in range(n_check):
        ids, dev = feed()
        first.append(ids)
        ts = time.perf_counter()
        prog["losses"].append(float(call(dev)))
        t_steps.append(time.perf_counter() - ts)
        if i == 0:
            run.phase("compile_or_cache")
            m = ref.leaf_norms_program(state["opt"]["m"], cfg)
            prog["grad_norms"] = {k: v / (1.0 - float(tr_cfg["beta1"]))
                                  for k, v in m.items()}
    p0 = ref.make_params(cfg, run.seed, layout="program")
    prog["change_norms"] = ref.leaf_norms_program(state["params"], cfg, other=p0)
    del p0
    run.phase("first_steps")
    est = min(t_steps[1:]) if len(t_steps) > 1 else t_steps[0]

    # ---- the window
    every = int(mix["loss_read_every"])
    pending, losses, n = collections.deque(), [], 0
    t0 = run.setup_done()
    t_end = t0 + run.seconds
    # the traced sub-window is the window's last seconds, and the trace
    # is stopped (which takes seconds) only once the window has closed
    loss = None
    while True:
        now = time.perf_counter()
        if now + est * (len(pending) + 0.5) >= t_end:
            break
        run.trace_if_due(now, t_end)
        _, dev = feed()
        loss = call(dev)
        pending.append(loss)
        n += 1
        if len(pending) > IN_FLIGHT - 1:
            with run.annotate("throttle"):
                pending.popleft().block_until_ready()
        if n % every == 0:
            with run.annotate("loss_read"):
                losses.append(float(loss))
    with run.annotate("loss_read"):
        last = float(loss)
    t1 = time.perf_counter()
    run.trace_stop()
    run.window_s = t1 - t0
    run.attempted, run.failed = n, int(not np.isfinite(losses + [last]).all())
    run.e2e["train_tok_s"] = n * rows * T / run.window_s
    run.counters.update({"steps": n, "rows": rows, "positions": T})
    run.memory_peak()
    run.say(f"window {run.window_s:.3f}s steps {n} rows {rows} positions {T} "
            f"last loss {last:.4f}")
    state.clear()
    del step, model
    gc.collect()
    run.reduce_trace()

    # ---- correctness: the reference follows the first steps
    t_ref = time.perf_counter()
    want = ref.train_reference(cfg, run.seed, first, tr_cfg, "f32",
                               int(mix["reference_rows_per_block"]))
    got = ref.compare_training(prog, want)
    run.say(f"reference: {n_check} steps in {time.perf_counter() - t_ref:.1f}s; "
            f"losses program {prog['losses']} reference {want['losses']}; "
            f"worst leaves {got['_at']}")
    run.samples.update(batches=first, program=prog, reference=want,
                       numbers={k: v for k, v in got.items() if k != "_at"})
    limits = mix["check"]["limits"]
    run.say("compared and not: " + ", ".join(
        f"{k}={v:.6g}" for k, v in got.items() if k != "_at"))
    for name, limit in limits.items():
        run.check(name, got[name], limit)
