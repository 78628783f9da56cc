"""The positions the DECODE PROGRAM attended against the positions the
reference selects, at the cell's own size:

    python3 benchmark/tools/selection_check.py --workload <cell> \\
        --seed 7 [--prompt-tokens 6000] [--new-tokens 48] [--rehearse file]

``correct`` compares served TOKENS with the reference, and a token
cannot show which 2,048 positions a step attended (PERF.md section 6,
PR 34: a selection kept from another layer reads inside the sound
runs). This tool reads the selection itself. It builds the engine as
the driver does, serves ONE request through ``submit``, and taps, in
every decode step and every attention layer, the list of positions
handed to ``sparse_latent_attention`` (an ordered ``jax.debug.callback``
on the two functions the model's module calls: the one difference from
the timed program). The reference is then run over the prompt and the
served tokens, and for each tapped step ``t`` and layer:

- ``count_off``: the program attended another NUMBER of positions than
  the reference selects (a top-k one short, attending everything);
- ``agree``: the share of the program's positions the reference
  selects too (1 but for near-ties: the program scores bfloat16 queries
  against bfloat16 keys, the reference float32 ones);
- ``beyond``: the share of the program's positions whose REFERENCE
  score lies under the reference's lowest selected score by more than
  ``--tolerance`` of the spread (std) of that query's scores: not a
  near-tie but a position the indexer in force would not have chosen (a
  selection kept from another layer, a shared layer scoring for
  itself, an approximate top-k).

The FIRST full layer scores the embeddings, which program and reference
hold bit for bit, so there (and in the layers that share its selection)
``agree`` and ``beyond`` read the indexer, the top-k and IndexShare
alone. A LATER full layer scores hidden states the program computed in
its own precision through every layer before: a token near a routing
tie gets another expert than the float32 reference gives it, its key
moves, and the reading holds that too (on the chip, PR 34: layer 0
agrees to 99.7% with ``beyond`` 0.001%, layer 4 to 94.6% with ``beyond``
4.4%; in float32 both agree position for position). So the two kinds
of layer have limits of their own: ``--limit`` and ``--limit-later``.

The last stdout line is one JSON object; ``selection_ok`` is false on
any ``count_off``, or where a layer's ``beyond`` passes its limit. A
benchmark run never calls this; ``PERF.md`` quotes what it printed.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import harness as h  # noqa: E402


def tap_selection(module, taps):
    """Wrap the two stage functions ``module`` (the model's) calls, so
    that every traced call reports, in program order: ("pos", pos [S])
    where a layer selects, ("attend", layer, sel [S, K], n_sel [S])
    where a layer attends."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    select, attend = module.index_select, module.sparse_latent_attention

    def index_select(qi, w, store, layer, tables, pos, top, **kw):
        jax.debug.callback(
            lambda p: taps.append(("pos", np.asarray(p))), pos, ordered=True)
        return select(qi, w, store, layer, tables, pos, top, **kw)

    def sparse_latent_attention(q, store, layer, tables, sel, n_sel, **kw):
        jax.debug.callback(
            lambda l, s, n: taps.append(
                ("attend", int(l), np.asarray(s), np.asarray(n))),
            jnp.int32(layer), sel, n_sel, ordered=True)
        return attend(q, store, layer, tables, sel, n_sel, **kw)

    module.index_select = index_select
    module.sparse_latent_attention = sparse_latent_attention

    def undo():
        module.index_select, module.sparse_latent_attention = select, attend
    return undo


def served_steps(taps, first_pos, steps):
    """{(step, layer): (positions the program attended)} of the one
    request, whose decode step ``i`` stands at position ``first_pos +
    i``; whatever else the engine ran (a warm-up, steps past the
    request's end) is left out."""
    out, lane, step = {}, None, None
    for ev in taps:
        if ev[0] == "pos":
            pos = ev[1]
            if lane is None and (pos == first_pos).any():
                lane = int((pos == first_pos).argmax())
            step = None
            if lane is not None and 0 <= pos[lane] - first_pos < steps:
                step = int(pos[lane] - first_pos)
        elif step is not None and (step, ev[1]) not in out:
            _, layer, sel, n_sel = ev
            out[(step, layer)] = sel[lane, :int(n_sel[lane])]
    return out


def reference_rows(ref, cfg, seed, ids, rows):
    """The reference over ``ids`` -> ({layer: bool [rows, T]}, the
    selection in force at each layer; {layer: float [rows, T]}, the
    index scores of each FULL layer) for the query positions ``rows``."""
    import jax.numpy as jnp
    import numpy as np

    z = ref.sizes(cfg)
    g = ref.make_globals(cfg, seed)
    hid = g["tok_emb"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    T, rows = len(ids), jnp.asarray(rows)
    masks, scores, mask = {}, {}, None
    for li in range(z["L"]):
        lp = ref._to_f32(ref.make_layer(cfg, seed, li))
        if z["idx"][li] == ref.FULL:
            x = ref._rms(hid, lp["in_norm"], z["eps"])
            c_q = ref._rms(ref._ein("td,dr->tr", x, lp["wq_a"], "f32"),
                           lp["q_norm"], z["eps"])
            qi = ref._rope_first(
                ref._ein("tr,re->te", c_q, lp["iwq"], "f32")
                .reshape(T, z["Hi"], z["Di"]), z["dr"], z["theta"])[rows]
            ki = ref._layer_norm(ref._ein("td,de->te", x, lp["iwk"], "f32"),
                                 lp["ik_gain"], lp["ik_bias"], z["ieps"])
            ki = ref._rope_first(ki[:, None, :], z["dr"], z["theta"])[:, 0]
            wi = ref._ein("td,dh->th", x, lp["iww"], "f32")[rows]
            s = ref._ein("qhd,kd->qhk", qi, ki, "f32")
            scores[li] = np.asarray(
                jnp.sum(jnp.maximum(s, 0.0) * wi[:, :, None], axis=1))
        hid, mask = ref.layer(lp, hid, mask, li, z)
        masks[li] = np.asarray(mask[rows])
    return masks, scores


def serve_one(cell, seed, prompt_tokens, new_tokens, taps):
    """One request through the engine as the driver builds it, the
    model's two stage functions tapped -> (prompt, served tokens). The
    engine, its pool and its weights are gone when this returns: the
    reference needs the chip's memory next."""
    import importlib

    import numpy as np
    from deeplearning4j_tpu.serving.engine import DecodeEngine

    cfg = cell.config
    model = cell.program.causal_lm(cfg)
    undo = tap_selection(importlib.import_module(type(model).__module__),
                         taps)
    try:
        params = cell.reference.make_params(cfg, seed, layout="program")
        engine = DecodeEngine(model, params, warm_start=False,
                              **cfg["deployment"]["engine"])
        del params
        engine.start()
        prompt = np.random.default_rng(seed).integers(
            0, int(cfg["vocab_size"]), prompt_tokens, dtype=np.int32)
        try:
            tokens = engine.submit(prompt, new_tokens, 0.0).result(
                timeout=1800)
        finally:
            engine.shutdown()
    finally:
        undo()
    return prompt, np.asarray(tokens, np.int32)


def check(cell, seed, prompt_tokens, new_tokens, tolerance=0.02, limit=0.001,
          limit_later=0.2, say=print):
    import gc

    import numpy as np
    from deeplearning4j_tpu.common.environment import configure_compile_cache

    configure_compile_cache()
    cfg, ref = cell.config, cell.reference
    taps = []
    prompt, tokens = serve_one(cell, seed, prompt_tokens, new_tokens, taps)
    gc.collect()
    steps = len(tokens) - 1       # the first token is the prefill's
    got = served_steps(taps, len(prompt), steps)
    say(f"served {len(tokens)} tokens behind {len(prompt)}; {len(taps)} "
        f"taps, {len(got)} of the request's (step, layer) pairs")
    ids = np.concatenate([prompt, tokens[:-1]])
    # the lengths check_served pads to, so that its programs serve
    pad = 128 if len(ids) <= 512 else 512 if len(ids) <= 4096 else 2048
    ids = np.pad(ids, (0, -len(ids) % pad))
    rows = len(prompt) + np.arange(steps)
    masks, scores = reference_rows(ref, cfg, seed, ids, rows)
    kinds = ref.sizes(cfg)["idx"]
    layers, ok = {}, bool(got)
    for li in sorted({l for _, l in got}):
        full = max(f for f in scores if f <= li)     # the indexer in force
        agree, beyond, deficits, off, n = [], 0, [0.0], 0, 0
        for i in range(steps):
            if (i, li) not in got:
                continue
            mine, t = got[(i, li)], int(rows[i])
            theirs = masks[li][i]
            off += int(len(mine) != int(theirs.sum()))
            agree.append(float(theirs[mine].mean()))
            I = scores[full][i]
            spread = float(I[:t + 1].std()) or 1.0
            short = (I[theirs].min() - I[mine[~theirs[mine]]]) / spread
            deficits.extend(short.tolist())
            beyond += int((short > tolerance).sum())
            n += len(mine)
        # how much of this layer's list the layer before it was handed
        # too: 1 under IndexShare, chance (top-k / context) between two
        # indexers that have nothing in common
        same = [float(np.isin(got[(i, li)], got[(i, li - 1)]).mean())
                for i in range(steps)
                if (i, li) in got and (i, li - 1) in got]
        layers[li] = {
            "indexer": kinds[li], "steps": len(agree), "count_off": off,
            "same_as_layer_before": float(np.mean(same)) if same else None,
            "agree_mean": float(np.mean(agree)), "agree_min": min(agree),
            "beyond_share": beyond / max(n, 1),
            "widest_deficit": float(max(deficits))}
        allowed = limit if full == min(scores) else limit_later
        ok &= off == 0 and layers[li]["beyond_share"] <= allowed
        say(f"layer {li} ({kinds[li]}): {layers[li]}")
    return {"workload": cell.name, "seed": seed,
            "prompt_tokens": int(len(prompt)), "steps": steps,
            "tolerance": tolerance, "limit": limit,
            "limit_later": limit_later, "layers": layers,
            "selection_ok": ok}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt-tokens", type=int, default=6000)
    ap.add_argument("--new-tokens", type=int, default=48)
    ap.add_argument("--tolerance", type=float, default=0.02)
    ap.add_argument("--limit", type=float, default=0.001)
    ap.add_argument("--limit-later", type=float, default=0.2)
    ap.add_argument("--rehearse", default=None)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    if root not in sys.path:
        sys.path.insert(0, root)         # the program, by its package
    cell = h.Cell(root, args.workload, args.rehearse)
    import jax

    report = check(cell, args.seed, args.prompt_tokens, args.new_tokens,
                   args.tolerance, args.limit, args.limit_later)
    dev = jax.devices()[0]
    report["device"] = {"platform": dev.platform, "kind": dev.device_kind}
    print(json.dumps(report))
    return 0 if report["selection_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
