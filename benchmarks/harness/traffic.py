"""The one general traffic generator. A mix is a data file under
`benchmarks/traffic/` naming a `generator` and its parameters; nothing
here knows a cell.

Every seed gets the SAME work: the sizes are the quantiles of the
mix's distributions (and, for `poisson`, of its arrival gaps), `pool` of
each, paired and dealt cycle by cycle by permutations drawn from the
mix's own `pool_seed`. `--seed` draws the token ids (and, in the
driver, the weights). In a closed loop the order IS the work: dealt per
seed, it alone moved the time to first token by a tenth between seeds
(PERF.md, PR 24). Another order is another mix: a copy of the file with
another `pool_seed`.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


def quantile(dist, u):
    """Inverse CDF of a length or gap distribution at u in (0, 1)."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(
            dist["sigma"] * statistics.NormalDist().inv_cdf(u))
    elif kind == "exponential":
        x = -dist["mean"] * math.log1p(-u)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(max(x, dist.get("min", x)), dist.get("max", x))


def size_pool(dist, n):
    """n sizes: the (i + 1/2)/n quantiles, rounded to whole tokens."""
    return [max(1, int(round(quantile(dist, (i + 0.5) / n))))
            for i in range(n)]


def with_rehearsal(params, rehearse):
    """A data file as run: its `rehearse` group laid over the top level
    when the run is a CPU rehearsal, and dropped otherwise."""
    out = {k: v for k, v in params.items() if k != "rehearse"}
    if rehearse:
        for k, v in params.get("rehearse", {}).items():
            if isinstance(v, dict) and isinstance(out.get(k), dict):
                out[k] = {**out[k], **v}
            else:
                out[k] = v
    return out


@dataclasses.dataclass
class Request:
    index: int
    prompt: list
    max_new_tokens: int
    due: float | None = None     # seconds after the start (poisson)
    sentinel: bool = False


class RequestSource:
    """Requests of a serving mix, by index: request i is the same for
    a seed whatever the timing of the run was."""

    def __init__(self, traffic, vocab_size, max_positions, seed):
        if traffic["generator"] not in ("closed_loop", "poisson"):
            raise ValueError(
                f"{traffic['generator']!r} generates no requests")
        self.traffic = traffic
        self.vocab = int(vocab_size)
        self.max_positions = int(max_positions)
        self.seed = int(seed)
        n = self.n = int(traffic["pool"])
        prompts = size_pool(traffic["prompt_len"], n)
        outputs = size_pool(traffic["output_len"], n)
        pairing = np.random.default_rng(
            int(traffic["pool_seed"])).permutation(n)
        self.sizes = [(prompts[i], outputs[int(pairing[i])])
                      for i in range(n)]
        self._orders = {}
        self._gaps, self._due = None, []
        if traffic["generator"] == "poisson":
            rate = float(traffic["rate_per_s"])
            self._gaps = [quantile({"dist": "exponential",
                                    "mean": 1.0 / rate},
                                   (i + 0.5) / n) for i in range(n)]

    def _order(self, cycle, stream):
        key = (cycle, stream)
        if key not in self._orders:
            self._orders[key] = np.random.default_rng(
                [int(self.traffic["pool_seed"]), stream,
                 cycle]).permutation(self.n)
        return self._orders[key]

    def _tokens(self, index, n):
        return np.random.default_rng(
            [self.seed, 7, index]).integers(0, self.vocab, n).tolist()

    def request(self, index):
        cycle, k = divmod(index, self.n)
        p_len, o_len = self.sizes[int(self._order(cycle, 1)[k])]
        p_len = min(p_len, self.max_positions - o_len)
        return Request(index, self._tokens(index, p_len), o_len,
                       due=self.due(index))

    def due(self, index):
        """When request `index` is due, in seconds after the start of
        the load: the running sum of the permuted gaps (poisson);
        None in a closed loop, where a request is due when its client
        is free."""
        if self._gaps is None:
            return None
        while len(self._due) <= index:
            cycle, k = divmod(len(self._due), self.n)
            gap = self._gaps[int(self._order(cycle, 2)[k])]
            self._due.append((self._due[-1] if self._due else 0.0) + gap)
        return self._due[index]

    def sentinel(self):
        """One fixed prompt whose greedy answer must not depend on its
        company: served alone in warm-up, once mid-window, and alone
        again after the drain."""
        s = self.traffic["sentinel"]
        toks = np.random.default_rng(
            [self.seed, 9]).integers(0, self.vocab,
                                     int(s["prompt_len"])).tolist()
        return Request(-1, toks, int(s["output_len"]), sentinel=True)

    def histogram(self, count):
        """Prompt and output lengths of the first `count` requests, as
        (min, median, max) each: for the lines before the result."""
        sizes = []
        for i in range(count):
            cycle, k = divmod(i, self.n)
            sizes.append(self.sizes[int(self._order(cycle, 1)[k])])
        if not sizes:
            return {}
        p = sorted(s[0] for s in sizes)
        o = sorted(s[1] for s in sizes)
        return {"prompt_len": (p[0], p[len(p) // 2], p[-1]),
                "output_len": (o[0], o[len(o) // 2], o[-1])}


def fixed_batches(traffic, seed):
    """Batch specifications of a training mix: `distinct_batches`
    (index, key seed) pairs; the driver makes each on the device from
    its key and uses them round-robin."""
    if traffic["generator"] != "fixed_batches":
        raise ValueError(f"{traffic['generator']!r} makes no batches")
    fold = int(seed) % (2 ** 31 - 1)
    return [{"index": i, "key_seed": (fold * 1009 + i) % (2 ** 31 - 1),
             "batch": int(traffic["batch"]),
             "seq_len": int(traffic["seq_len"])}
            for i in range(int(traffic["distinct_batches"]))]
