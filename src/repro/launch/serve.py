"""Serving driver: batched decode / recsys scoring / live graph serving.

``python -m repro.launch.serve --arch mixtral-8x7b --tokens 32`` runs
prefill + a decode loop on the smoke config (CPU); on a TPU mesh the same
code path serves the full config under the serve sharding rules.

``python -m repro.launch.serve --graph block-rmat --window 4096`` instead
runs the live partition-serving loop: a sliding-window S5P chain churns in
a background ingest thread, each step published as an atomic
partition-bundle swap, while a GAS PageRank reader executes super-steps
and point queries over the pinned versions (see ``repro.serving``).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_arch
from ..models import lm as LM
from ..models import recsys as R
from ..runtime.compile_cache import enable_compile_cache


def serve_lm(arch: str, prompt_len: int = 32, gen_tokens: int = 16,
             batch: int = 2, smoke: bool = True, seed: int = 0):
    spec = get_arch(arch)
    cfg = spec.smoke_config if smoke else spec.config
    key = jax.random.PRNGKey(seed)
    params = LM.init_params(cfg, key)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab,
                                 dtype=jnp.int32)
    max_seq = prompt_len + gen_tokens
    prefill_jit = jax.jit(lambda p, t: LM.prefill(p, t, cfg, max_seq=max_seq))
    decode_jit = jax.jit(lambda p, c, t, pos: LM.decode_step(p, c, t, pos, cfg))
    t0 = time.time()
    logits, cache = prefill_jit(params, prompts)
    toks = jnp.argmax(logits, axis=-1)
    out = [toks]
    for i in range(gen_tokens - 1):
        pos = jnp.full((batch,), prompt_len + i, jnp.int32)
        logits, cache = decode_jit(params, cache, toks, pos)
        toks = jnp.argmax(logits, axis=-1)
        out.append(toks)
    seqs = jnp.stack(out, axis=1)
    jax.block_until_ready(seqs)
    dt = time.time() - t0
    print(f"[serve] {arch}: {batch}×{gen_tokens} tokens in {dt:.2f}s "
          f"({dt / gen_tokens * 1e3:.1f} ms/token)")
    return seqs


def serve_recsys(arch: str = "xdeepfm", batch: int = 64, smoke: bool = True,
                 seed: int = 0):
    spec = get_arch(arch)
    cfg = spec.smoke_config if smoke else spec.config
    key = jax.random.PRNGKey(seed)
    params = R.xdeepfm_init(cfg, key)
    cols = [jax.random.randint(jax.random.fold_in(key, f), (batch,), 0, v,
                               dtype=jnp.int32) for f, v in enumerate(cfg.vocabs())]
    ids = jnp.stack(cols, axis=1)
    fwd = jax.jit(lambda p, x: R.xdeepfm_forward(p, x, cfg))
    t0 = time.time()
    scores = fwd(params, ids)
    jax.block_until_ready(scores)
    print(f"[serve] {arch}: scored {batch} in {(time.time()-t0)*1e3:.1f} ms")
    return scores


def serve_graph(graph: str = "block-rmat", k: int = 8,
                window_edges: int = 4096, step_edges: int | None = None,
                supersteps_per_swap: int = 4, queries_per_swap: int = 2,
                auto_cold_restart: bool = True, background: bool = False,
                seed: int = 0, verbose: bool = True):
    """Live partition-serving loop: churn + GAS super-steps + queries.

    Builds a sliding-window S5P chain over ``graph``'s edge stream and a
    :class:`~repro.serving.ServingController` that publishes each step's
    live window as an atomic :class:`~repro.serving.PartitionBundle`
    swap.  A :class:`~repro.serving.GASServer` interleaves PageRank
    super-steps and point queries against the pinned versions — with
    ``background=True`` the ingest runs on its own thread and the reader
    free-runs against whatever version is current (the deployment shape);
    otherwise churn and compute interleave deterministically.  Returns
    ``(server, controller)`` for inspection.
    """
    from ..core.s5p import S5PConfig
    from ..graphs import block_rmat_graph, community_graph
    from ..incremental import S5PWindowChain
    from ..serving import BundleRegistry, GASServer, ServingController

    if graph == "block-rmat":
        src, dst, n = block_rmat_graph(block_scale=6, n_blocks=16,
                                       edge_factor=8, seed=seed)
    else:
        src, dst, n = community_graph(4096, n_communities=32, seed=seed)
    cfg = S5PConfig(k=k, seed=seed, chunk_size=max(window_edges, 1024))
    chain = S5PWindowChain(src, dst, n, cfg, window_edges,
                           step_edges=step_edges,
                           auto_cold_restart=auto_cold_restart)
    registry = BundleRegistry()
    controller = ServingController(registry, chain)
    server = GASServer(registry)
    rng = np.random.default_rng(seed)
    t0 = time.time()
    if background:
        controller.start(throttle_s=0.001)
        while not controller.done.is_set() or registry.current is None:
            if server.superstep() is None:
                time.sleep(0.001)
                continue
            server.query_pagerank(rng.integers(0, n, 16))
            if controller.done.is_set():
                break
        controller.join()
    else:
        while controller.step() is not None:
            if registry.current is None:
                continue  # window still filling
            for _ in range(supersteps_per_swap):
                server.superstep()
            for _ in range(queries_per_swap):
                server.query_pagerank(rng.integers(0, n, 16))
    server.run_to_convergence()
    if verbose:
        s = server.metrics.summary()
        print(f"[serve] graph={graph} V={n} E={src.size} k={k} "
              f"window={window_edges}")
        print(f"[serve] versions={controller.version} "
              f"swaps_observed={s['swaps_observed']} "
              f"supersteps={s['supersteps']} "
              f"bytes/superstep={s['sync_bytes_per_superstep']:.0f} "
              f"rf={s['rf_final']:.3f} "
              f"query_lat={s['query_latency_us_mean']:.0f}us "
              f"wall={time.time() - t0:.1f}s")
    return server, controller


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--graph", default=None,
                    help="serve a live-partitioned graph instead of a "
                         "model: block-rmat | community")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--step-edges", type=int, default=None)
    ap.add_argument("--background", action="store_true",
                    help="run ingest on a background thread (free-running "
                         "reader) instead of deterministic interleave")
    ap.add_argument("--no-cold-restart", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    if args.graph is not None:
        serve_graph(args.graph, k=args.k, window_edges=args.window,
                    step_edges=args.step_edges, background=args.background,
                    auto_cold_restart=not args.no_cold_restart)
    elif get_arch(args.arch).family == "recsys":
        serve_recsys(args.arch, batch=args.batch)
    else:
        serve_lm(args.arch, gen_tokens=args.tokens, batch=args.batch)


if __name__ == "__main__":
    main()
