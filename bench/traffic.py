"""The one traffic generator: turns a mix's data file and a seed into the
requests of one run.

A mix (``bench/traffic/<name>.json``) is parameters only:

``loop``         ``"open"``: requests are sent at due times fixed in
                 advance, whatever the server does; ``"closed"``: each of
                 ``clients`` clients keeps one request outstanding.
``rate_per_s``   open loop: mean arrivals per second.
``on_off``       open loop, optional: ``{"period_s", "on_share"}``;
                 arrivals fall only in the first ``on_share`` of each
                 period, at ``rate_per_s / on_share`` there.
``clients``      closed loop: number of clients.
``rows``         ``{rows: count}``: the multiset of request sizes.  Sizes
                 are drawn from it without replacement and it is refilled
                 when empty, so every seed sends the same sizes in another
                 order.

An open loop sends ``round(rate_per_s * seconds)`` requests at times drawn
uniformly over the window's on-phases: a Poisson process conditioned on
its count, so every seed offers the same load.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterator, List, Tuple

import numpy as np

MAX_ROWS = 8


def load(path: Path) -> Dict:
    """A mix's parameters, checked."""
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    rows = {int(k): int(v) for k, v in mix["rows"].items()}
    if not rows or min(rows) < 1 or max(rows) > MAX_ROWS \
            or min(rows.values()) < 1:
        raise ValueError(f"{path}: rows must map sizes 1..{MAX_ROWS} to "
                         "positive counts")
    mix["rows"] = rows
    if mix["loop"] == "open" and not mix.get("rate_per_s", 0) > 0:
        raise ValueError(f"{path}: an open loop needs rate_per_s > 0")
    if mix["loop"] == "closed" and not int(mix.get("clients", 0)) >= 1:
        raise ValueError(f"{path}: a closed loop needs clients >= 1")
    on_off = mix.get("on_off")
    if on_off is not None and not (on_off["period_s"] > 0
                                   and 0 < on_off["on_share"] <= 1):
        raise ValueError(f"{path}: on_off needs period_s > 0 and "
                         "0 < on_share <= 1")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator for one use of ``seed`` (any integer)."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


def sizes(mix: Dict, rng: np.random.Generator) -> Iterator[int]:
    """Request sizes: the mix's multiset, shuffled, again and again."""
    bag = np.repeat(list(mix["rows"]), list(mix["rows"].values()))
    while True:
        yield from (int(s) for s in rng.permutation(bag))


def arrivals(mix: Dict, seed: int, seconds: float) -> List[Tuple[float, int]]:
    """Open loop: ``(due second from the window's start, rows)`` for every
    request, in due order."""
    rng = rng_for(seed, 1)
    n = int(round(mix["rate_per_s"] * seconds))
    on_off = mix.get("on_off")
    if on_off is None:
        due = np.sort(rng.uniform(0.0, seconds, n))
    else:
        period, share = on_off["period_s"], on_off["on_share"]
        # uniform over the on-phases, then mapped back onto the clock
        on_total = sum(min(share * period, seconds - t0)
                       for t0 in np.arange(0.0, seconds, period))
        u = np.sort(rng.uniform(0.0, on_total, n))
        on_len = share * period
        due = (u // on_len) * period + u % on_len
    it = sizes(mix, rng_for(seed, 2))
    return [(float(t), next(it)) for t in due]


def client_sizes(mix: Dict, seed: int) -> List[Iterator[int]]:
    """Closed loop: each client's endless sequence of request sizes."""
    return [sizes(mix, rng_for(seed, 10 + c))
            for c in range(int(mix["clients"]))]


def mean_rows(mix: Dict) -> float:
    n = sum(mix["rows"].values())
    return sum(k * v for k, v in mix["rows"].items()) / n
