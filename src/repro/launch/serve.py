"""Serving driver: one-shot batch generation or a continuous-batching loop.

One-shot (fixed batch, every row same prompt length and gen):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
        --batch 4 --prompt-len 32 --gen 32

Continuous batching (trace-driven scheduler, per-request lengths, KV-cache
request slots — see ``launch/scheduler.py``):

    PYTHONPATH=src python -m repro.launch.serve --arch dbrx-132b --reduced \
        --continuous --requests 16 --max-batch 4 --host-moe

MoE architectures can route decode-step expert dispatch through the
process's shared ReapRuntime (``--host-moe``): the decode step stays jitted
and only the routing pattern crosses to the host via ``jax.pure_callback``
into the registered ``moe_dispatch`` op, so repeated per-token routings hit
warm bundling plans and — with ``--plan-store`` — server restarts reuse the
plans a previous process inspected.

``--exec-store DIR`` makes the *compiled programs* durable too: the
continuous scheduler's prefill/decode executables persist via
``runtime/exec_store.py``, so a restarted server reaches its first
streamed token with zero XLA compiles (``--expect-zero-compiles`` turns
that into a gated assertion — the tier1.yml warm-restart smoke).  All
runtime flags come from the shared ``repro.runtime.add_runtime_args``
group; the runtime is built once via ``RuntimeConfig.from_args`` and
installed with ``set_default_runtime``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, reduced_config
from repro.launch.compile_cache import init_compile_cache
from repro.models import model as M


def generate(cfg, params, tokens, *, gen: int, max_seq: int,
             temperature: float = 0.0, seed: int = 0, frames=None,
             host_moe: bool = False):
    """Greedy/temperature sampling. tokens: (B, prompt_len) int32.

    Decode steps are always jitted.  When a host runtime is installed
    (``models.moe.set_host_dispatch_runtime``), the compiled decode step's
    MoE layers route their slot destinations through a ``jax.pure_callback``
    into the registry's ``moe_dispatch`` op — warm plans are hit from
    *inside* compiled code, with no eager unroll.  ``host_moe`` is kept for
    API compatibility; it no longer changes the decode path.
    """
    del host_moe  # runtime installation alone selects the callback path

    def decode_fn(p, c, t, pos):
        return M.decode_step(cfg, p, c, t, pos)

    b, prompt_len = tokens.shape
    decode = jax.jit(decode_fn)
    if cfg.enc_dec:
        cache = M.init_cache(cfg, b, max_seq, s_enc=frames.shape[1])
        _, cache = M.encdec_prefill(cfg, params, frames, cache)
        # consume the prompt token by token (decoder side)
        logits = None
        for i in range(prompt_len):
            logits, cache = decode(params, cache, tokens[:, i:i + 1],
                                   jnp.int32(i))
    else:
        cache = M.init_cache(cfg, b, max_seq)
        prefill = jax.jit(lambda p, t, c: M.prefill(cfg, p, t, c))
        logits, cache = prefill(params, tokens, cache)
        logits = logits[:, -1:]

    key = jax.random.PRNGKey(seed)
    out = [tokens]
    cur = None
    lat = []
    for i in range(gen):
        pos = prompt_len + i - 1 if not cfg.enc_dec else prompt_len + i - 1
        if cur is None:
            step_logits = logits[:, -1]
        else:
            t0 = time.time()
            step_logits, cache = decode(params, cache, cur, jnp.int32(pos))
            step_logits = step_logits[:, -1]
            jax.block_until_ready(step_logits)
            lat.append(time.time() - t0)
        if temperature > 0:
            key, sub = jax.random.split(key)
            cur = jax.random.categorical(
                sub, step_logits / temperature)[:, None].astype(jnp.int32)
        else:
            cur = jnp.argmax(step_logits, axis=-1)[:, None].astype(jnp.int32)
        out.append(cur)
    return jnp.concatenate(out, axis=1), lat


def _store_op_report(rt) -> str:
    """Warm-plan counts per registered op tag (registry-enumerated).

    Chunked fingerprints ("spgemm_gather_chunked") attribute to the
    registry op that owns them ("spgemm_gather") via the specs'
    ``fingerprint_ops`` declarations."""
    from repro.runtime.ops import op_tag_for_fingerprint
    counts: dict = {}
    for fp in rt.store.fingerprints():
        tag = op_tag_for_fingerprint(fp.op) or "other"
        counts[tag] = counts.get(tag, 0) + 1
    parts = [f"{tag}={n}" for tag, n in sorted(counts.items())]
    return " ".join(parts) if parts else "none"


def _capability_report() -> str:
    """One line per registered op from its declared capability metadata.

    Enumerated from ``list_ops()`` + ``capability_summary`` so newly
    admitted ops show up here with zero serve edits; routers own no
    plans and are marked as such instead of echoing capabilities."""
    from repro.runtime.ops import capability_summary, get_op, list_ops
    lines = []
    for tag in list_ops():
        spec = get_op(tag)
        if spec.route is not None:
            lines.append(f"  {tag}: (router)")
            continue
        cap = capability_summary(spec)
        chunk = "+chunked" if cap["chunked"] else ""
        shard = "+shardable" if cap["shardable"] else ""
        lines.append(f"  {tag}: [{','.join(cap['dtypes'])}] "
                     f"{cap['routing']}{chunk}{shard}")
    return "\n".join(lines)


def _resolve_routing(mode: str) -> dict:
    """Per-op serving route, decided from declared ``OpCapabilities``.

    ``auto`` takes each concrete op's own ``routing`` declaration — an op
    that declares ``in_graph`` has a traced twin and stays inside the
    compiled step; one that declares ``host`` runs through the eager
    registry path.  ``host``/``in_graph`` force every concrete op one way
    (the override the capability system exists to make safe: capabilities
    say which ops *can* take it).  Routers are skipped — they own no
    execution path.
    """
    from repro.runtime.ops import capability_summary, get_op, list_ops
    routes = {}
    for tag in list_ops():
        spec = get_op(tag)
        if spec.route is not None:
            continue
        declared = capability_summary(spec)["routing"]
        routes[tag] = declared if mode == "auto" else mode
    return routes


def serve_continuous(cfg, args, rt):
    """Trace-driven continuous-batching serve (the scheduler front end)."""
    from repro.launch.scheduler import ServeScheduler, synthetic_trace
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    trace = synthetic_trace(args.requests, seed=args.seed,
                            vocab=cfg.vocab_size)
    streamed = [0]

    def on_token(rid, token, step):
        streamed[0] += 1

    sch = ServeScheduler(cfg, params, max_batch=args.max_batch,
                         max_seq=args.max_seq,
                         token_budget=args.token_budget, on_token=on_token)
    if args.prewarm:
        t0 = time.time()
        n = sch.prewarm([len(r.prompt) for r in trace])
        print(f"[serve] prewarmed {n} prefill bucket(s) in "
              f"{time.time() - t0:.2f}s"
              + (" (persisted to the exec store)"
                 if rt is not None and rt.exec is not None else ""))
    t0 = time.time()
    completions = sch.run(trace)
    total = time.time() - t0
    new_tokens = sum(len(c.tokens) for c in completions)
    print(f"[serve] continuous: {len(completions)}/{args.requests} requests"
          f" in {sch.stats['steps']} steps ({sch.stats['decode_steps']} "
          f"decode), {new_tokens} tokens in {total:.2f}s "
          f"({new_tokens / total:.1f} tok/s), {streamed[0]} streamed")
    lat = sch.latency_summary()
    print(f"[serve] latency: ttft p50={lat['ttft']['p50_s'] * 1e3:.1f}ms "
          f"p99={lat['ttft']['p99_s'] * 1e3:.1f}ms "
          f"(n={lat['ttft']['n']}); decode step "
          f"p50={lat['decode_step']['p50_s'] * 1e3:.1f}ms "
          f"p99={lat['decode_step']['p99_s'] * 1e3:.1f}ms "
          f"(n={lat['decode_step']['n']})")
    occupancy = M.cache_slot_occupancy(sch.cache)
    if occupancy.any():
        raise SystemExit(f"[serve] ERROR: drained scheduler left orphaned "
                         f"KV slots: {occupancy.tolist()}")
    if args.expect_completions is not None:
        if len(completions) != args.expect_completions or streamed[0] == 0:
            raise SystemExit(
                f"[serve] ERROR: expected {args.expect_completions} "
                f"completions with streamed tokens, got "
                f"{len(completions)} / {streamed[0]} streamed")
        print(f"[serve] smoke OK: {args.expect_completions} completions, "
              f"{streamed[0]} streamed tokens, no orphaned slots")
    return completions


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="gemma2-2b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the tiny same-family CPU smoke config "
                         "(default); --no-reduced serves the published "
                         "widths and depth")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="serve a synthetic request trace through the "
                         "continuous-batching scheduler instead of one "
                         "fixed batch (per-request prompt/gen lengths, "
                         "KV-cache slot reuse, per-step streaming)")
    ap.add_argument("--requests", type=int, default=16,
                    help="[--continuous] trace length")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="[--continuous] decode slots (KV-cache rows)")
    ap.add_argument("--max-seq", type=int, default=64,
                    help="[--continuous] per-slot cache length")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="[--continuous] admission budget in resident "
                         "tokens (prompt+gen per in-flight request)")
    ap.add_argument("--expect-completions", type=int, default=None,
                    help="[--continuous] exit nonzero unless exactly this "
                         "many requests complete with streamed output "
                         "(CI smoke gate)")
    ap.add_argument("--expect-zero-compiles", action="store_true",
                    help="[--continuous --exec-store] exit nonzero unless "
                         "the serve completed with zero XLA compilations "
                         "and >= 1 executable loaded from the store (CI "
                         "warm-restart gate — run the same command twice)")
    ap.add_argument("--host-moe", action="store_true",
                    help="route decode-step MoE dispatch through the "
                         "runtime's registered moe_dispatch op via "
                         "jax.pure_callback — decode stays jitted; only "
                         "the routing pattern leaves the graph. Repeated "
                         "per-token routings hit warm bundling plans; with "
                         "--plan-store they survive restarts. Legacy alias "
                         "for --routing=host")
    ap.add_argument("--routing", choices=("auto", "host", "in_graph"),
                    default="auto",
                    help="per-op dispatch route: 'auto' follows each "
                         "registered op's declared OpCapabilities.routing "
                         "(in_graph ops stay inside the compiled step, "
                         "host ops go through the eager registry path); "
                         "'host'/'in_graph' force every op one way")
    ap.add_argument("--prewarm", action="store_true",
                    help="[--continuous] compile (or load from the exec "
                         "store) the prefill program for every prompt-"
                         "length bucket in the trace before serving — all "
                         "prefill compiles leave the serving window, and "
                         "with --exec-store every bucket's executable is "
                         "persisted for warm restarts")
    from repro.runtime import add_runtime_args
    add_runtime_args(ap)
    args = ap.parse_args(argv)
    init_compile_cache()
    if args.host_moe and args.routing == "auto":
        args.routing = "host"            # legacy alias keeps its meaning

    rt = None
    if (args.plan_store or args.exec_store or args.host_moe
            or args.routing == "host"):
        from repro.runtime import (ReapRuntime, RuntimeConfig,
                                   set_default_runtime)
        rt = set_default_runtime(
            ReapRuntime(RuntimeConfig.from_args(args)))
        if rt.store is not None:
            s = rt.store.summary()
            print(f"[serve] plan store {args.plan_store}: {s['entries']} "
                  f"warm plans ({_store_op_report(rt)}), "
                  f"{s['bytes'] / 1e6:.2f} MB on disk")
        if rt.exec is not None:
            es = rt.exec.store.summary()
            print(f"[serve] exec store {args.exec_store}: {es['entries']} "
                  f"compiled executables, {es['bytes'] / 1e6:.2f} MB on "
                  f"disk")
        print("[serve] registered ops (dtypes/routing, registry-enumerated):")
        print(_capability_report())

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    # route selection ACTS on declared capabilities: moe_dispatch is the op
    # the decode step can route host-side, so its resolved route decides
    # whether the host dispatch runtime gets installed
    routes = _resolve_routing(args.routing)
    host_moe = routes.get("moe_dispatch") == "host"
    if host_moe and cfg.ffn != "moe":
        # no MoE layers → nothing to route through the runtime
        if args.host_moe or args.routing == "host":
            print(f"[serve] note: host routing has no effect on {args.arch}"
                  " (no MoE layers)")
        host_moe = False
    if host_moe and rt is None:
        from repro.runtime import (ReapRuntime, RuntimeConfig,
                                   set_default_runtime)
        rt = set_default_runtime(ReapRuntime(RuntimeConfig.from_args(args)))
    if rt is not None:
        print(f"[serve] routing ({args.routing}): " + " ".join(
            f"{tag}={route}" for tag, route in sorted(routes.items())))
    if host_moe:
        # decode stays fully jitted (scan_layers included): the MoE decode
        # branch hops to the host through pure_callback for dest only
        from repro.models.moe import set_host_dispatch_runtime
        set_host_dispatch_runtime(rt)
    if args.continuous:
        seqs = serve_continuous(cfg, args, rt)
    else:
        params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
        rng = np.random.default_rng(args.seed)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                          (args.batch, args.prompt_len)),
                             jnp.int32)
        frames = None
        if cfg.enc_dec:
            frames = jnp.asarray(rng.standard_normal(
                (args.batch, args.prompt_len, cfg.d_frame)), jnp.float32)
        max_seq = args.prompt_len + args.gen + 1
        t0 = time.time()
        seqs, lat = generate(cfg, params, tokens, gen=args.gen,
                             max_seq=max_seq, temperature=args.temperature,
                             seed=args.seed, frames=frames,
                             host_moe=host_moe)
        total = time.time() - t0
        print(f"[serve] {args.batch} seqs × {args.gen} new tokens in "
              f"{total:.2f}s ({args.batch * args.gen / total:.1f} tok/s)")
        if lat:
            print(f"[serve] decode latency p50={np.median(lat) * 1e3:.1f}ms "
                  f"p99={np.percentile(lat, 99) * 1e3:.1f}ms")
        print("[serve] first sequence:", np.asarray(seqs[0])[:16], "...")
    if host_moe:
        from repro.models.moe import set_host_dispatch_runtime
        set_host_dispatch_runtime(None)
    if rt is not None:
        cs = rt.cache_stats()
        line = (f"[serve] plan cache: {cs['hits']} hits, "
                f"{cs['store_hits']} store hits, {cs['misses']} misses")
        if rt.store is not None:
            line += (f"; store holds {cs['store']['entries']} plans "
                     f"({cs['store']['saves']} saved this run)")
        print(line)
        active = {tag: rec for tag, rec in cs["per_op"].items()
                  if any(rec.values())}
        if active:
            print("[serve] per-op:", " ".join(
                f"{tag}[h={rec['hits']},s={rec['store_hits']},"
                f"m={rec['misses']},warm={rec['warm_rate']:.2f}]"
                for tag, rec in sorted(active.items())))
        elif rt.store is not None:
            print("[serve] note: no sparse op consulted the runtime this "
                  "run — the jitted decode path routes in-graph; pass "
                  "--host-moe on an MoE arch to route dispatch through it")
        if rt.exec is not None:
            ex = rt.exec.summary()
            print(f"[serve] exec cache: {ex['compiles']} XLA compiles, "
                  f"{ex['loads']} loaded from store, {ex['saves']} "
                  f"persisted, {ex['unserializable']} kept process-local "
                  f"(host callbacks)")
    if args.expect_zero_compiles:
        if rt is None or rt.exec is None:
            raise SystemExit("[serve] ERROR: --expect-zero-compiles "
                             "requires --exec-store")
        ex = rt.exec.summary()
        if ex["compiles"] != 0 or ex["loads"] < 1:
            raise SystemExit(
                f"[serve] ERROR: warm restart expected zero XLA compiles "
                f"and >=1 store load, got {ex['compiles']} compiles / "
                f"{ex['loads']} loads (store: {ex.get('store')})")
        print(f"[serve] warm-restart OK: zero XLA compiles, "
              f"{ex['loads']} executables loaded from the store")
    return seqs


if __name__ == "__main__":
    main()
