"""Batched serving driver: prefill + decode through one jitted decode step
(deliverable (b): serving example).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \\
      --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..core.options import SessionOptions
from ..models.api import Model
from ..models.params import init_params
from ..obs import metrics as obs_metrics
from .cli import (add_cluster_options, add_model_options, add_obs_options,
                  enable_compile_cache)


def print_metrics(label: str = "serve") -> None:
    """One-line §16.4 registry digest: serving latency percentiles when
    any request completed, plus the distrib counters when non-zero."""
    snap = obs_metrics.snapshot()
    lat = snap["histograms"].get("serving.request_latency_s")
    parts = []
    if lat and lat.get("count"):
        parts.append(f"latency p50={lat['p50']*1e3:.1f}ms "
                     f"p99={lat['p99']*1e3:.1f}ms n={lat['count']}")
    for name, v in snap["counters"].items():
        if v and name.startswith(("distrib.", "serving.")):
            parts.append(f"{name}={v}")
    print(f"[{label}] metrics: " + ("; ".join(parts) or "empty"))


def serve(arch: str = "qwen2-0.5b", *, smoke: bool = False, batch: int = 4,
          prompt_len: int = 16, gen: int = 32, max_seq: int = 128,
          seed: int = 0, temperature: float = 0.0) -> Dict[str, Any]:
    """Prefill and decode a batch of random prompts with one jitted decode
    step, the cache donated.  This loop stays beside the batcher
    (``repro.serving``) because it alone fills an encoder-decoder's cross
    cache.

    Returns the ``generated`` token ids, the ``prompts`` and
    ``prompt_logits`` — the logits at the last prompt position, as the
    cache-filling steps produced them — plus prefill/decode timings."""
    cfg = get_config(arch, smoke=smoke)
    model = Model.for_config(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    cache = init_params(
        model.init_cache_desc(batch=batch, max_seq=max_seq),
        jax.random.PRNGKey(1))

    rs = np.random.RandomState(seed)
    prompts = jnp.array(rs.randint(0, cfg.vocab_size, (batch, prompt_len)),
                        jnp.int32)
    frames = None
    if model.is_encdec:
        from ..models import encdec

        frames = jnp.array(
            (rs.randn(batch, cfg.enc_seq, cfg.d_model) * 0.1).astype("f"))
        enc_out = encdec.encode(cfg, model.plan, params, frames)
        ck, cv = encdec.build_cross_cache(cfg, model.plan, params, enc_out)
        cache["cross_k"], cache["cross_v"] = ck, cv

    # params go in as an argument: closed over, they would be baked into
    # the executable as constants (2.5 GB for qwen2-0.5b); the cache is
    # donated, so each token's k/v are written in place
    decode = jax.jit(model.serve_step, donate_argnums=(1,))

    # --- prefill: feed prompt tokens one step at a time (the cache fills);
    # production prefill lowers the batched forward (launch/steps.py).
    t0 = time.time()
    logits = None
    for t in range(prompt_len):
        logits, cache = decode(params, cache, prompts[:, t:t + 1],
                               jnp.array(t))
    prefill_s = time.time() - t0
    prompt_logits = logits

    # --- decode: greedy (or temperature) sampling, batched
    out_tokens = []
    key = jax.random.PRNGKey(seed + 1)
    tok = jnp.argmax(logits[:, 0, : cfg.vocab_size], axis=-1)[:, None]
    t0 = time.time()
    for t in range(prompt_len, prompt_len + gen):
        out_tokens.append(np.asarray(tok))
        logits, cache = decode(params, cache, tok.astype(jnp.int32),
                               jnp.array(t))
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits[:, 0, : cfg.vocab_size] / temperature)[:, None]
        else:
            tok = jnp.argmax(logits[:, 0, : cfg.vocab_size], axis=-1)[:, None]
    decode_s = time.time() - t0

    gen_arr = np.concatenate(out_tokens, axis=1)
    tput = batch * gen / decode_s if decode_s > 0 else float("inf")
    print(f"[serve] arch={cfg.arch_id} batch={batch} "
          f"prefill {prefill_s:.2f}s "
          f"decode {decode_s:.2f}s ({tput:.1f} tok/s)")
    return {"generated": gen_arr, "prompts": prompts,
            "prompt_logits": prompt_logits, "prefill_s": prefill_s,
            "decode_s": decode_s, "tokens_per_s": tput}


def serve_cluster(cluster: str, *, batch: int = 32, requests: int = 100,
                  seed: int = 0, log_every: int = 25,
                  trace_dir: Optional[str] = None,
                  metrics_every: int = 0) -> Dict[str, Any]:
    """DESIGN.md §11 distributed scoring loop over a TCP worker pool.

    Serves the wire-shippable primitive-op MLP's logits: the forward
    graph is partitioned across the ``--cluster`` workers once
    (RegisterGraph), then every request re-runs the cached Executable —
    one RunGraph fan-out with the hidden activations crossing processes
    through the wire rendezvous.  The steady state is the paper's
    serving shape (§3.2 "caches these graphs"), process boundaries
    included.  (The LM decode graph is §15 factory-form and would ship
    too; the MLP keeps this loop fast and dependency-free.)
    """
    from ..core import Session
    from ..distrib.wire import ClusterSpec
    from .steps import build_wire_train_step

    spec = ClusterSpec.parse(cluster)
    tasks = [f"/job:worker/task:{t}" for t in range(len(spec.workers))]
    ws = build_wire_train_step(tasks, seed=seed)
    sess = Session(ws.builder.graph,
                   options=SessionOptions(cluster=spec, trace_dir=trace_dir))
    # fetching only the logits prunes the whole loss/grad/update subgraph
    # (§4.2), so the shipped graph is the pure forward pass
    run = sess.make_callable([ws.logits], [ws.feed_x])
    rs = np.random.RandomState(seed)
    t0 = time.time()
    last = None
    try:
        for r in range(requests):
            x = jnp.asarray(rs.randn(batch, 16).astype("f"))
            t_req = time.time()
            (last,) = run(x)
            obs_metrics.histogram("serving.request_latency_s").observe(
                time.time() - t_req)
            if (r + 1) % log_every == 0:
                rate = (r + 1) / (time.time() - t0)
                print(f"[serve] request {r+1:4d} "
                      f"({rate:.1f} req/s over the wire)")
            if metrics_every and (r + 1) % metrics_every == 0:
                print_metrics()
    finally:
        stats = sess.cache_stats
        sess.close()
    total = time.time() - t0
    rate = requests / total if total > 0 else float("inf")
    print(f"[serve] cluster={','.join(spec.workers)} batch={batch} "
          f"requests={requests} ({rate:.1f} req/s, cache {stats})")
    return {"requests_per_s": rate, "executable_cache": stats,
            "last_logits_shape": tuple(np.asarray(last).shape)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    add_model_options(ap, arch="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    add_cluster_options(ap)
    add_obs_options(ap)
    ap.add_argument("--requests", type=int, default=100,
                    help="number of scoring requests in --cluster mode")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.cluster:
        serve_cluster(args.cluster, batch=args.batch, requests=args.requests,
                      trace_dir=args.trace_dir,
                      metrics_every=args.metrics_every)
        return 0
    res = serve(args.arch, smoke=args.smoke, batch=args.batch,
                prompt_len=args.prompt_len, gen=args.gen)
    print("[serve] sample token ids:", res["generated"][0][:16].tolist())
    if args.metrics_every:
        print_metrics()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
