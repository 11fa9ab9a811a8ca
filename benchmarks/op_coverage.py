"""Registry-driven per-op coverage shared by the benchmark scripts.

Every consumer here enumerates ``runtime.ops.list_ops()`` and drives each
concrete (non-router) op through a ``ReapRuntime`` using the shared
example problems in ``repro.analysis.op_examples`` — the same table the
dynamic purity harness replays.  Registering a new op makes it appear in
``bench_plan_cache``, ``fig6`` and ``fig10`` output with zero benchmark
edits; a registered op *without* an example problem is reported as a
coverage gap and fails the verdict instead of being silently skipped.
"""
from __future__ import annotations

import time
from typing import Dict, List

from repro.analysis.op_examples import builtin_examples
from repro.runtime import ReapRuntime, get_op, list_ops
from repro.runtime.ops import capability_summary


def concrete_ops() -> List[str]:
    """Registered tags that own plans (routers resolve to these)."""
    return [tag for tag in list_ops() if get_op(tag).route is None]


def _caps(tag: str) -> Dict:
    """Declared capability metadata for a tag, JSON-friendly."""
    cap = capability_summary(get_op(tag))
    return dict(dtypes=list(cap["dtypes"]), routing=cap["routing"],
                chunked=cap["chunked"])


def per_op_breakdown(reduced: bool = False, verbose: bool = True) -> dict:
    """Exercise every registered op through ONE runtime (miss, then hit)
    and report the per-op-tag hit/miss/store-hit split from
    ``cache_stats()["per_op"]``."""
    n = 512 if reduced else 1024
    examples = builtin_examples(n)
    rt = ReapRuntime(n_chunks=1, use_pallas=False, block=64)

    covered, skipped = [], []
    for tag in concrete_ops():
        ex = examples.get(tag)
        if ex is None:
            skipped.append(tag)
            continue
        rt.run(tag, *ex.operands(0), **ex.kw)      # miss (cold)
        rt.run(tag, *ex.operands(1), **ex.kw)      # hit (same pattern)
        covered.append(tag)
    per_op = {tag: dict(rec, capabilities=_caps(tag))
              for tag, rec in rt.cache_stats()["per_op"].items()
              if tag in covered}
    ok = not skipped and all(rec["hits"] >= 1 and rec["misses"] >= 1
                             for rec in per_op.values())
    row = dict(bench="per_op_breakdown", registered=list_ops(),
               per_op=per_op, skipped=skipped, ok=ok)
    if verbose:
        for tag, rec in sorted(per_op.items()):
            print(f"plan_cache,per_op,{tag},hits={rec['hits']},"
                  f"store_hits={rec['store_hits']},misses={rec['misses']}")
        for tag in skipped:
            print(f"plan_cache,per_op,{tag},SKIPPED(no example problem)")
        print(f"plan_cache,per_op,verdict,"
              f"{'PASS' if ok else 'FAIL'}(hit+miss per registered op)")
    return row


def per_op_warm_rows(n: int = 384, repeats: int = 3, verbose: bool = True,
                     prefix: str = "bench") -> List[Dict]:
    """Cold (miss) vs warm (hit) wall time for every registered op.

    The figure scripts append these rows so their per-op amortization
    columns track the registry instead of a hand-kept op list.
    """
    examples = builtin_examples(n)
    rows: List[Dict] = []
    for tag in concrete_ops():
        ex = examples.get(tag)
        if ex is None:
            rows.append(dict(bench=f"{prefix}_per_op", op=tag, ok=False,
                             skipped=True))
            if verbose:
                print(f"{prefix}_per_op,{tag},SKIPPED(no example problem)")
            continue
        rt = ReapRuntime(n_chunks=1, **ex.runtime_kw)
        t0 = time.perf_counter()
        rt.run(tag, *ex.operands(0), **ex.kw)
        cold_s = time.perf_counter() - t0
        warm_s = []
        hit = True
        for r in range(1, repeats + 1):
            operands = ex.operands(r)       # same pattern, fresh values
            t0 = time.perf_counter()
            _, st = rt.run(tag, *operands, **ex.kw)
            warm_s.append(time.perf_counter() - t0)
            hit = hit and st["cache_hit"]
        warm = min(warm_s)
        caps = _caps(tag)
        rows.append(dict(bench=f"{prefix}_per_op", op=tag, n=n,
                         cold_s=cold_s, warm_s=warm,
                         speedup=cold_s / max(warm, 1e-9), ok=hit,
                         skipped=False, capabilities=caps))
        if verbose:
            print(f"{prefix}_per_op,{tag},cold_ms={cold_s * 1e3:.1f},"
                  f"warm_ms={warm * 1e3:.1f},"
                  f"speedup={cold_s / max(warm, 1e-9):.2f},"
                  f"{'hit' if hit else 'MISS(!)'},"
                  f"dtypes={'|'.join(caps['dtypes'])},"
                  f"routing={caps['routing']}"
                  f"{'+chunked' if caps['chunked'] else ''}")
    return rows
