"""The one generator of load. A traffic mix or training job is a data
file of parameters under ``chipbench/traffic/``; this reads it and makes
the inputs from ``--seed``. Every seed gets the same sizes and gaps, so
that runs differ in nothing but token values (a seed that changes the
amount or the order of work reads as noise, PERF.md Findings PR 23).

Serving arrivals copy ``benchmarks/serve_bench.py``'s seeded open-loop
schedule: a list of due times fixed before the run starts."""

import math

import numpy as np


def _quantile_set(n, lo, hi, law, rng):
    """``n`` values spread evenly over the quantiles of ``law`` on
    [lo, hi], shuffled by ``rng``."""
    u = (np.arange(n) + 0.5) / n
    if law == "log_uniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif law == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown law {law!r}")
    return rng.permutation(np.clip(np.rint(x), lo, hi).astype(np.int64))


def _snap(x, grid):
    """Round each value to the nearest member of ``grid``: the program
    compiles a little per distinct answer length, so a mix names a grid
    that set-up can warm."""
    grid = np.asarray(sorted(grid))
    return grid[np.abs(x[:, None] - grid[None, :]).argmin(1)]


def answer_grid(mix):
    a = mix["answer_tokens"]
    n = a["distinct"]
    u = (np.arange(n) + 0.5) / n
    return sorted({int(round(math.exp(
        math.log(a["min"]) + t * (math.log(a["max"]) - math.log(a["min"])))))
        for t in u})


def serve_requests(mix, seed, seconds, vocab):
    """The whole schedule of one run: a list of dicts with ``due_s``
    (seconds from the start of load; the measured window opens at
    ``mix['lead_in_s']``), ``prompt`` (int32 array), ``max_new`` and
    ``in_window``.

    The mix fixes ONE period of traffic as long as the window: Poisson
    arrivals at ``rate_per_s`` (the gaps are the quantiles of an
    exponential law, shuffled by the mix's own ``schedule_seed``) and the
    lengths beside them. Load is that period repeated, before the window
    (lead-in) and after it (until the sample has finished). ``--seed``
    picks the phase at which the window cuts the period, and every token:
    so every seed's window holds the same requests at the same gaps, in
    another (rotated) order, and runs differ by nothing else."""
    base = np.random.default_rng([mix["schedule_seed"], 1])
    n = int(round(mix["rate_per_s"] * seconds))
    u = (np.arange(n) + 0.5) / n
    gaps = base.permutation(-np.log1p(-u))
    starts = np.cumsum(gaps) - gaps[0]
    starts *= seconds / (starts[-1] + gaps[0])   # one period, to the second
    p, a = mix["prompt_tokens"], mix["answer_tokens"]
    prompts = _quantile_set(n, p["min"], p["max"], p["law"], base)
    answers = _snap(_quantile_set(n, a["min"], a["max"], a["law"], base),
                    answer_grid(mix))
    rng = np.random.default_rng([seed, 1])
    lead, tail = mix["lead_in_s"], mix["drain_limit_s"]
    out = []
    for period in range(-int(np.ceil(lead / seconds)),
                        1 + int(np.ceil(tail / seconds))):
        for k in range(n):
            due = lead + period * seconds + starts[k]
            if 0.0 <= due < lead + seconds + tail:
                out.append({
                    "index": len(out), "due_s": float(due),
                    "prompt": rng.integers(0, vocab, int(prompts[k])).astype(
                        np.int32),
                    "max_new": int(answers[k]), "in_window": period == 0})
    return out


def zipf_cdf(vocab, exponent):
    w = 1.0 / np.arange(1, vocab + 1) ** exponent
    return np.cumsum(w / w.sum())


class TrainFeed:
    """Batches of a training job: step ``i``'s rows are drawn from
    (seed, i), every row different, token ids from a Zipf law over the
    vocabulary (a unigram structure the loss can fall on). ``rows`` is the
    global batch; a row holds ``seq + 1`` tokens (inputs and targets)."""

    def __init__(self, job, seed, vocab, chips):
        self.seed, self.vocab = seed, vocab
        self.rows = job["rows_per_chip"] * chips
        self.width = job["seq"] + 1
        self._cdf = zipf_cdf(vocab, job["zipf_exponent"])
        self._perm = np.random.default_rng([seed, 2]).permutation(vocab)

    def batch(self, step):
        rng = np.random.default_rng([self.seed, 3, step])
        ranks = np.searchsorted(self._cdf, rng.random((self.rows, self.width)))
        return self._perm[np.minimum(ranks, self.vocab - 1)].astype(np.int32)
