"""Seeded stand-in for a crawled web graph.

:func:`web_graph` returns a directed sparsity pattern ``(indptr, indices)``
(CSR, int64, columns sorted within a row; row i lists the pages page i
links to) with exactly the rows and nonzeros its configuration states, as
``bench.generators`` and ``bench.meshes`` do for their stand-ins.
:func:`pattern_of` draws a configuration's one pattern from its
``pattern_seed``; values come from the run's seed
(``bench.generators.normal_values``).
"""
from __future__ import annotations

import numpy as np

from bench.generators import _csr_from_pairs

PARAMS = ("rows", "nnz", "host_median", "host_sigma", "out_exponent",
          "empty_share", "max_out", "local_share", "local_bias",
          "global_bias", "oversample")


def _host_sizes(rng: np.random.Generator, n: int, median: float,
                sigma: float) -> np.ndarray:
    """Lognormal host sizes (at least one page) summing to exactly ``n``:
    drawn until they cover ``n`` pages, the last host cut to fit."""
    batch = max(16, int(2 * n / median))
    parts, total = [], 0
    while total < n:
        s = np.maximum(1, np.rint(median * np.exp(
            rng.normal(0.0, sigma, batch)))).astype(np.int64)
        parts.append(s)
        total += int(s.sum())
    sizes = np.concatenate(parts)
    last = int(np.searchsorted(np.cumsum(sizes), n))
    sizes = sizes[:last + 1]
    sizes[-1] -= int(sizes.sum()) - n
    return sizes


def _pick(rng: np.random.Generator, cum: np.ndarray, lo: np.ndarray,
          hi: np.ndarray) -> np.ndarray:
    """One page in ``[lo, hi)`` per entry, with probability proportional to
    the weights whose running sum is ``cum`` (``cum[0] == 0``)."""
    x = cum[lo] + rng.random(lo.shape[0]) * (cum[hi] - cum[lo])
    return np.clip(np.searchsorted(cum, x, side="right") - 1, lo, hi - 1)


def web_graph(rng: np.random.Generator, *, rows: int, nnz: int,
              host_median: float, host_sigma: float, out_exponent: float,
              empty_share: float, max_out: int, local_share: float,
              local_bias: float, global_bias: float, oversample: float):
    """Pattern of a crawl of ``rows`` pages with heavy-tailed degrees.

    Pages belong to hosts whose sizes follow a lognormal law (median
    ``host_median``, log-sd ``host_sigma``); each host is a contiguous
    block of ids, hosts and their pages in the seeded order drawn.  A
    share ``empty_share`` of pages links nowhere (the crawl's frontier);
    the others draw an out-degree from a Pareto law of density exponent
    ``out_exponent``, capped at ``max_out``, scaled so that ``oversample``
    links are drawn per link kept.

    Each host keeps one ordered link list, as long as its largest
    out-degree: an entry is a page of the same host with probability
    ``local_share``, picked with weight ``(out-degree + 1) ** local_bias``,
    and otherwise any page, with weight ``(out-degree + 1) **
    global_bias``.  A page of out-degree d links to the first d entries of
    its host's list (navigation first), so pages of one host share their
    leading links; ``local_bias`` and ``global_bias`` make links favour
    pages of many out-links.  Self-links and repeats are dropped, then
    links at random until exactly ``nnz`` are left.
    """
    n, nnz = int(rows), int(nnz)
    sizes = _host_sizes(rng, n, host_median, host_sigma)
    n_hosts = sizes.shape[0]
    starts = np.concatenate([[0], np.cumsum(sizes)])
    host = np.repeat(np.arange(n_hosts), sizes)

    tail = (1.0 - rng.random(n)) ** (-1.0 / (out_exponent - 1.0))
    deg = np.minimum(np.floor(tail), max_out)
    deg[rng.random(n) < empty_share] = 0.0
    deg = np.minimum(np.rint(deg * (oversample * nnz / deg.sum())),
                     max_out).astype(np.int64)

    list_len = np.zeros(n_hosts, np.int64)
    np.maximum.at(list_len, host, deg)
    offset = np.concatenate([[0], np.cumsum(list_len)])
    entry_host = np.repeat(np.arange(n_hosts), list_len)
    local = rng.random(entry_host.shape[0]) < local_share
    cum_local = np.concatenate([[0.0], np.cumsum((deg + 1.0) ** local_bias)])
    cum_global = np.concatenate([[0.0],
                                 np.cumsum((deg + 1.0) ** global_bias)])
    entries = np.empty(entry_host.shape[0], np.int64)
    h = entry_host[local]
    entries[local] = _pick(rng, cum_local, starts[h], starts[h + 1])
    far = int((~local).sum())
    entries[~local] = _pick(rng, cum_global, np.zeros(far, np.int64),
                            np.full(far, n, np.int64))

    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    rank = np.arange(src.shape[0]) - np.repeat(np.cumsum(deg) - deg, deg)
    dst = entries[offset[host[src]] + rank]
    keep = dst != src
    pairs = np.unique(src[keep] * n + dst[keep])
    if pairs.shape[0] < nnz:
        raise ValueError(f"the links drawn give {pairs.shape[0]} distinct "
                         f"pairs; {nnz} are needed")
    pairs = rng.choice(pairs, nnz, replace=False)
    indptr, indices = _csr_from_pairs(n, pairs // n, pairs % n)
    assert indices.shape[0] == nnz
    return indptr, indices


def pattern_of(config: dict):
    """The configuration's pattern, drawn from its own ``pattern_seed``
    whatever the run's seed."""
    rng = np.random.default_rng(int(config["pattern_seed"]))
    return web_graph(rng, **{key: config[key] for key in PARAMS})
