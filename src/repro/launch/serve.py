"""Serving driver: prefill + batched decode through the production step
builders, on the host mesh at reduced scale (the dry-run lowers the same
functions at mesh scale).

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --tokens 16

``--federated`` flips the driver into the personalized serving plane
(DESIGN.md §3d): train a small federated LM population with
`run_federated(keep_state=True)` (or load a checkpointed `DeltaStore`),
ingest the per-user personalized params into a codec-compressed
`DeltaStore`, and serve per-user greedy decode — each user's prompt runs
through THEIR OWN reconstructed params via the `ServeEngine` micro-batcher
(one gather+decode and one vmapped prefill/decode_step per batch), with
the §3d parity anchor checked on every flush.

    PYTHONPATH=src python -m repro.launch.serve --federated \
        --rounds 4 --clients 4 --codec qsgd:4 --save-store /tmp/store.msgpack
    PYTHONPATH=src python -m repro.launch.serve --federated \
        --store /tmp/store.msgpack --requests 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.models import scan as scan_mod
from repro.models import transformer as T
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import init_model_params, _use_scan


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="mamba2-780m")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--tokens", type=int, default=16)
    p.add_argument("--cache-len", type=int, default=128)
    p.add_argument("--seed", type=int, default=0)
    # ---- personalized serving plane (DESIGN.md §3d) ----
    p.add_argument("--federated", action="store_true",
                   help="serve per-user personalized models from a "
                        "DeltaStore (train first, or --store to load)")
    p.add_argument("--preset", default="cpu-small",
                   choices=("cpu-small", "lm-100m", "full"),
                   help="federated: LM preset (launch.train grammar)")
    p.add_argument("--algorithm", default="ucfl_k2",
                   help="federated: strategy registry spec")
    p.add_argument("--rounds", type=int, default=4)
    p.add_argument("--local-steps", type=int, default=1)
    p.add_argument("--clients", type=int, default=4)
    p.add_argument("--pool", type=int, default=16,
                   help="federated: sequences per client dataset")
    p.add_argument("--codec", default="identity",
                   help="federated: at-rest delta codec — identity | "
                        "qsgd:<bits> | topk:<frac>")
    p.add_argument("--placement", default="host", choices=("host", "mesh"),
                   help="federated: where batches decode and land")
    p.add_argument("--store", default="",
                   help="federated: load a checkpointed DeltaStore instead "
                        "of training")
    p.add_argument("--save-store", default="",
                   help="federated: checkpoint the built DeltaStore here")
    p.add_argument("--requests", type=int, default=8,
                   help="federated: number of decode requests to serve")
    p.add_argument("--max-batch", type=int, default=4,
                   help="federated: micro-batcher chunk size")
    args = p.parse_args(argv)
    enable_compile_cache()
    if args.federated:
        return federated_main(args)
    return smoke_main(args)


def smoke_main(args):
    """Single un-personalized smoke model through prefill/decode_step."""
    cfg = get_smoke_config(args.arch)
    # independent streams per use: params init, prompt tokens and the
    # audio/vision embeds each get their own subkey
    kparams, ktok, kembed = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    params = init_model_params(kparams, cfg)
    use_scan = _use_scan(cfg)
    B = args.batch

    batch = {"tokens": jax.random.randint(ktok, (B, args.prompt_len), 0,
                                          cfg.vocab_size)}
    if cfg.family == "audio":
        batch["audio_embeds"] = jax.random.normal(
            kembed, (B, cfg.encoder.n_ctx, cfg.d_model))
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            kembed, (B, cfg.vision.n_tokens, cfg.vision.embed_dim))
    prefix = cfg.vision.n_tokens if cfg.family == "vlm" else 0

    caches = T.make_caches(cfg, B, args.cache_len, jnp.float32)
    if use_scan:
        caches = scan_mod.stack_caches(caches, cfg)
        prefill = jax.jit(lambda p, b, c: scan_mod.prefill(p, cfg, b, c))
        decode = jax.jit(lambda p, t, c, pos: scan_mod.decode_step(
            p, cfg, t, c, pos))
    else:
        prefill = jax.jit(lambda p, b, c: T.prefill(p, cfg, b, c))
        decode = jax.jit(lambda p, t, c, pos: T.decode_step(p, cfg, t, c, pos))

    t0 = time.time()
    logits, caches = prefill(params, batch, caches)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    print(f"prefill {args.prompt_len} tokens x{B}: {time.time()-t0:.2f}s")

    out_tokens = [tok]
    t0 = time.time()
    for i in range(args.tokens - 1):
        pos = jnp.full((B,), prefix + args.prompt_len + i, jnp.int32)
        logits, caches = decode(params, tok, caches, pos)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    dt = time.time() - t0
    toks = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print(f"decoded {args.tokens-1} steps x{B} in {dt:.2f}s "
          f"({(args.tokens-1)*B/max(dt,1e-9):.1f} tok/s)")
    print("sample:", toks[0][:16])
    return toks


def build_decode_one(cfg, prompt_len: int, n_tokens: int, cache_len: int):
    """Per-user greedy decode, ONE user's params x ONE prompt -> token ids.

    The same `prefill`/`decode_step` the smoke path and the launch.steps
    case builders wrap — the ServeEngine vmaps it over the request batch,
    so a chunk of B users runs as one batched prefill + n_tokens batched
    decode steps through each user's own reconstructed params."""
    use_scan = _use_scan(cfg)

    def decode_one(params, tokens):
        batch = {"tokens": tokens[None]}
        caches = T.make_caches(cfg, 1, cache_len, jnp.float32)
        if use_scan:
            caches = scan_mod.stack_caches(caches, cfg)
            logits, caches = scan_mod.prefill(params, cfg, batch, caches)
        else:
            logits, caches = T.prefill(params, cfg, batch, caches)
        step = scan_mod.decode_step if use_scan else T.decode_step
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out = [tok]
        for i in range(n_tokens - 1):
            pos = jnp.full((1,), prompt_len + i, jnp.int32)
            logits, caches = step(params, cfg, tok[:, None], caches, pos)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            out.append(tok)
        return jnp.concatenate(out)

    return decode_one


def federated_main(args):
    """Train-then-serve (or load a store) — the §3d serving plane."""
    from repro.fl import (FLConfig, HostVmap, MeshShardMap, run_federated)
    from repro.fl.serve import DeltaStore, ServeEngine, check_parity
    from repro.launch.train import _lm_fns, lm_federated_data

    cfg, loss_fn, acc_fn = _lm_fns(args.arch, args.preset)
    placement = (MeshShardMap(schedule="shard_map_streams")
                 if args.placement == "mesh" else HostVmap())
    backend = placement.codec_backend

    if args.store:
        store = DeltaStore.load(args.store)
        print(f"loaded store {args.store}: {store.summary()}")
    else:
        m = args.clients
        fed = lm_federated_data(
            jax.random.fold_in(jax.random.PRNGKey(args.seed), 1), m,
            pool=args.pool, n_val=4, seq=args.prompt_len,
            vocab=cfg.vocab_size)
        fl = FLConfig(rounds=args.rounds, local_steps=args.local_steps,
                      batch_size=4, eval_every=max(1, args.rounds // 2))
        t0 = time.time()
        h = run_federated(args.algorithm, fed, fl=fl, placement=placement,
                          model_init=lambda k: init_model_params(k, cfg),
                          loss_fn=loss_fn, acc_fn=acc_fn,
                          keep_state=True, seed=args.seed)
        print(f"trained {args.algorithm} m={m} rounds={args.rounds} "
              f"final -CE={h.mean_acc[-1]:.4f} ({time.time()-t0:.0f}s)")
        store = DeltaStore.from_history(h, codec=args.codec, backend=backend)
        print(f"store[{args.codec}]: {store.summary()}")
    if args.save_store:
        store.save(args.save_store)
        print("store written:", args.save_store)

    decode_one = build_decode_one(cfg, args.prompt_len, args.tokens,
                                  max(args.cache_len, args.prompt_len
                                      + args.tokens))
    engine = ServeEngine(store, decode_one, placement=placement,
                         max_batch=args.max_batch)

    # per-user prompts on independent streams (the RNG-hygiene rule the
    # smoke path follows: one fold per user)
    kreq = jax.random.fold_in(jax.random.PRNGKey(args.seed), 2)
    users = [int(u) for u in np.arange(args.requests) % store.m]
    prompts = {
        u: jax.random.randint(jax.random.fold_in(kreq, u),
                              (args.prompt_len,), 0, cfg.vocab_size,
                              dtype=jnp.int32)
        for u in set(users)}
    tickets = [engine.submit(u, prompts[u]) for u in users]
    t0 = time.time()
    outs = engine.flush()
    dt = time.time() - t0
    del tickets
    # §3d parity anchor on the served batch: gather-then-decode output ==
    # direct forward through the reference reconstruction, bit-identical
    probe = sorted(set(users))[:args.max_batch]
    check_parity(engine, probe, np.stack([prompts[u] for u in probe]))
    stats = engine.last_stats
    lat = stats["latency_s"]
    print(f"served {stats['requests']} requests in {stats['batches']} "
          f"batches, {dt:.2f}s ({stats['requests']/max(dt, 1e-9):.1f} "
          f"req/s), per-batch p50={np.percentile(lat, 50)*1e3:.0f}ms "
          f"max={max(lat)*1e3:.0f}ms — parity anchor OK")
    for u, o in list(zip(users, outs))[:4]:
        print(f"user {u}: {np.asarray(o)[:12]}")
    return outs


if __name__ == "__main__":
    main()
