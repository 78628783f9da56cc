"""The one general traffic generator: a mix is a data file of
parameters, and this turns it and ``--seed`` into requests or batches.

A mix is a fixed trace of lengths: they are the evenly spaced quantiles
of the mix's distributions (no draw), dealt to the clients by the mix's
own ``deal_seed``. ``--seed`` draws only the token ids (and, elsewhere,
the weights). So every seed offers the same requests in the same order
with other content, and two seeds never differ in the work on offer: a
window that ends after a third of the pool would otherwise see other
lengths, joins and completions from seed to seed.

A length distribution is a file ``lengths/<dist>.py`` with one function
``at_quantiles(spec, q)``, found by the name in the mix.
"""

import importlib.util
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _distribution(name):
    path = os.path.join(HERE, "lengths", name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"unknown length distribution {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location("benchmark_lengths_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quantile_lengths(spec, n):
    """``n`` lengths at the quantiles (i + 0.5) / n of ``spec``, cut to
    its ``min`` and ``max``."""
    q = (np.arange(n) + 0.5) / n
    x = np.asarray(_distribution(spec["dist"]).at_quantiles(spec, q))
    return np.clip(np.floor(x), spec.get("min", 1),
                   spec.get("max", math.inf)).astype(np.int64)


def serving_requests(mix, vocab_size, seed):
    """-> per client, a list of {"prompt": ids, "max_new_tokens": n}.
    The pool of ``clients * requests_per_client`` (prompt, output)
    length pairs and their order are the mix's (``deal_seed``); the
    seed draws the ids. With ``first_output_fraction: "uniform"`` client
    ``c``'s first request keeps the share (k + 0.5) / clients of its
    output length, k dealt too, so completions are spread from the
    window's first second."""
    deal = np.random.default_rng(int(mix["deal_seed"]))
    rng = np.random.default_rng(int(seed))
    clients = int(mix["clients"])
    per = int(mix["requests_per_client"])
    n = clients * per
    prompts = deal.permutation(quantile_lengths(mix["prompt_len"], n))
    outputs = deal.permutation(quantile_lengths(mix["output_len"], n))
    shares = deal.permutation((np.arange(clients) + 0.5) / clients)
    plan = []
    for c in range(clients):
        reqs = []
        for i in range(per):
            k = c * per + i
            out = int(outputs[k])
            if i == 0 and mix.get("first_output_fraction") == "uniform":
                lo = int(mix["output_len"].get("min", 1))
                out = max(lo, int(math.ceil(out * shares[c])))
            reqs.append({
                "prompt": rng.integers(0, vocab_size, int(prompts[k]),
                                       dtype=np.int32),
                "max_new_tokens": out})
        plan.append(reqs)
    return plan


def training_batches(rows, positions, vocab_size, seed):
    """An endless stream of id batches [rows, positions + 1], uniform
    over the vocabulary, every row different, made on the host."""
    rng = np.random.default_rng(int(seed))
    while True:
        yield rng.integers(0, vocab_size, (rows, positions + 1), dtype=np.int32)
