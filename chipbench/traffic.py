"""The one traffic generator: turns a mix file (``traffic/<mix>.json``) and
a seed into requests.

Lengths are stratified: a run of ``n`` requests takes the distribution's
quantiles at ``(i + 0.5) / n`` and a shuffle puts them in order, so every
seed does the same amount of work.  Open-loop gaps are drawn the same way.
The shuffle comes from the mix's ``order_seed`` where it gives one (then
every run replays one fixed schedule of lengths and arrivals, and the run's
seed draws only the token ids), else from the run's seed.  Token ids are
uniform over the published vocabulary, from a stream of their own per
request.

Mix keys:

* ``loop``: ``open`` (requests due on a schedule whatever the server does)
  or ``closed`` (``clients`` callers, each sending its next request when
  the previous one has finished);
* ``arrival``: for an open loop, ``{"process": "poisson", "rate_per_s": r}``;
* ``prompt_tokens`` / ``output_tokens``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
* ``cycle``: for a closed loop, how many requests one shuffled cycle of
  quantiles holds;
* ``order_seed``: where given, the seed of the shuffles above, the same for
  every run.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Req:
    uid: int
    prompt: list[int]
    max_new: int
    due_s: float | None = None     # open loop: seconds after the window opens
    client: int | None = None      # closed loop: which caller sends it


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The distribution's ``n`` stratified quantiles, as whole tokens."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
        vals = np.rint(vals)
    elif spec["dist"] == "uniform":
        vals = np.floor(spec["min"] + u * (spec["max"] - spec["min"] + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(vals, spec["min"], spec["max"]).astype(int)


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *salt])


def _order(mix: dict, seed: int) -> int:
    """The seed that orders the lengths and gaps of a run at ``seed``."""
    return mix.get("order_seed", seed)


def _prompt(seed: int, uid: int, n: int, vocab: int) -> list[int]:
    return _rng(seed, 1, uid).integers(1, vocab, n).tolist()


def open_loop(mix: dict, seconds: float, seed: int, vocab: int,
              rate: float | None = None) -> list[Req]:
    """Every request due in a window of ``seconds``, sorted by due time.
    ``rate`` overrides the mix's (the knee sweep)."""
    rate = rate or mix["arrival"]["rate_per_s"]
    if mix["arrival"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']['process']!r}")
    n = max(1, round(rate * seconds))
    rng = _rng(_order(mix, seed), 0)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    due = np.cumsum(gaps)
    due *= seconds * (n - 0.5) / n / due[-1]   # the mix's rate, exactly
    p = rng.permutation(quantiles(mix["prompt_tokens"], n))
    o = rng.permutation(quantiles(mix["output_tokens"], n))
    return [Req(uid=i, prompt=_prompt(seed, i, int(p[i]), vocab),
                max_new=int(o[i]), due_s=float(due[i])) for i in range(n)]


class ClosedLoop:
    """The requests of ``clients`` callers: client ``c`` sends requests
    ``c, c + clients, ...`` of an endless sequence made of shuffled cycles
    of the same ``cycle`` quantiles."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.clients = mix["clients"]
        self.cycle = mix.get("cycle", 64)
        self._p = quantiles(mix["prompt_tokens"], self.cycle)
        self._o = quantiles(mix["output_tokens"], self.cycle)
        self._sent = [0] * self.clients

    def _nth(self, uid: int) -> tuple[int, int]:
        c, i = divmod(uid, self.cycle)
        rng = _rng(_order(self.mix, self.seed), 2, c)
        return (int(rng.permutation(self._p)[i]),
                int(rng.permutation(self._o)[i]))

    def next(self, client: int) -> Req:
        uid = self._sent[client] * self.clients + client
        self._sent[client] += 1
        p, o = self._nth(uid)
        return Req(uid=uid, prompt=_prompt(self.seed, uid, p, self.vocab),
                   max_new=o, client=client)


def longest(mix: dict) -> int:
    """Most positions one request of the mix can take."""
    return mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]


def prefill_heads(mix: dict) -> tuple[int, int]:
    """(shortest, longest) prompt head a prefill sees (prompt less the
    last token, which the first decode step replays)."""
    return mix["prompt_tokens"]["min"] - 1, mix["prompt_tokens"]["max"] - 1
