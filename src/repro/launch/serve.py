"""Serving driver: paged continuous batching (default) or dense waves.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch llama-100m --smoke --requests 8 --prompt-len 32 --gen 16

Two engines behind one driver:

``--engine paged`` (default where the config supports it) runs
:class:`repro.serve.engine.PagedEngine`: a global pool of fixed-size KV
blocks, per-request block tables, chunked prefill interleaved with
decode waves, and decode batches assembled per wave from live sequences
— true continuous batching.  KV exhaustion degrades through the
admission queue (shed / deferred-then-expired) instead of crashing.

``--engine dense`` is the static-batch baseline: one prefill per wave of
up to ``--batch`` requests into per-slot dense caches, then decode until
every sequence in the wave has finished.  Slots without a live sequence
are masked out of token emission and the wave ends as soon as the
longest request is done, so heterogeneous ``max_new`` no longer decodes
dead slots to the global maximum.

Graceful degradation (:class:`AdmissionQueue`): admission beyond
``--max-queue`` pending requests is SHED at submit, a request that waits
past ``--deadline-s`` is EXPIRED at the next wave take, and the paged
engine OOM-sheds requests that can never fit its KV pool.  All three
leave explicit status markers instead of unbounded waiting.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig, SyntheticLMDataset
from repro.distributed.context import mesh_context
from repro.launch.mesh import make_context, smoke_context
from repro.models.api import build_model
from repro.models.transformer import paged_supported
from repro.serve.engine import PagedEngine
from repro.serve.sampling import sample_tokens as _sample


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    out_tokens: list = field(default_factory=list)
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    status: str = "queued"    # queued | done | expired | shed


class AdmissionQueue:
    """Bounded FIFO admission with per-request queue deadlines.

    Pure host-side policy (no model, no jax) so overload behaviour is
    unit-testable: ``submit`` sheds beyond ``max_queue`` pending entries,
    ``take_wave`` first expires entries whose queue wait exceeds
    ``deadline_s`` and then hands out up to ``batch`` survivors in FIFO
    order.  ``max_queue=0`` / ``deadline_s=0`` disable the respective
    limit.  Rejected requests are kept (with their status marker) on the
    ``shed`` / ``expired`` lists so the caller can report them instead of
    leaving clients waiting forever.

    The paged engine adds two verbs for its KV-pool OOM policy:
    ``shed_now`` (request can never fit — reject outright) and ``defer``
    (request doesn't fit *yet* — requeue at the FRONT with its original
    ``t_submit``, so under sustained pressure the normal deadline
    machinery expires it rather than the engine spinning on it forever).
    """

    def __init__(self, max_queue: int = 0, deadline_s: float = 0.0):
        self.max_queue = max_queue
        self.deadline_s = deadline_s
        self.pending: list[Request] = []
        self.shed: list[Request] = []
        self.expired: list[Request] = []

    def __len__(self) -> int:
        return len(self.pending)

    def submit(self, req: Request, now: float | None = None) -> bool:
        """Admit ``req`` (True) or shed it (False) when the queue is full."""
        if req.t_submit == 0.0:
            req.t_submit = time.time() if now is None else now
        if self.max_queue and len(self.pending) >= self.max_queue:
            req.status = "shed"
            self.shed.append(req)
            return False
        req.status = "queued"
        self.pending.append(req)
        return True

    def shed_now(self, req: Request) -> None:
        """Reject a request the engine cannot ever serve (KV OOM-shed)."""
        req.status = "shed"
        self.shed.append(req)

    def defer(self, req: Request) -> None:
        """Requeue at the front, keeping t_submit (deadline still ticking)."""
        req.status = "queued"
        self.pending.insert(0, req)

    def _expire(self, now: float) -> None:
        if not self.deadline_s:
            return
        keep = []
        for r in self.pending:
            if now - r.t_submit > self.deadline_s:
                r.status = "expired"
                self.expired.append(r)
            else:
                keep.append(r)
        self.pending = keep

    def take_wave(self, batch: int, now: float | None = None
                  ) -> list[Request]:
        """Expire overdue entries, then pop up to ``batch`` requests."""
        self._expire(time.time() if now is None else now)
        wave = self.pending[:batch]
        del self.pending[:batch]
        return wave


# ---------------------------------------------------------------------------
# Dense baseline (static waves, per-slot dense caches)
# ---------------------------------------------------------------------------


def run_dense(cfg, bundle, params, queue: AdmissionQueue, *,
              batch: int, prompt_len: int, temperature: float = 0.0,
              seed: int = 0) -> dict:
    """Wave-at-a-time serving against dense per-slot KV caches.

    All requests in a wave share one prefill (prompts must share
    ``prompt_len``); the wave then decodes until its longest request
    finishes — not to a fixed global step count — and slots whose
    request is already done (or that were batch padding) emit nothing.
    """
    max_new_cap = max((r.max_new for r in queue.pending), default=1)
    max_len = prompt_len + max_new_cap + 8
    prefill = jax.jit(lambda p, b: bundle.prefill(p, b, max_len))
    decode = jax.jit(bundle.decode_step, donate_argnums=(1,))
    key = jax.random.PRNGKey(seed)
    done: list[Request] = []
    B = batch
    t0 = time.time()
    n_decode_calls = 0
    n_samples = 0

    while len(queue):
        wave = queue.take_wave(B)
        if not wave:
            break
        # pad free slots with zero rows, not repeats of slot 0
        toks = np.zeros((B, prompt_len), np.int32)
        for i, r in enumerate(wave):
            toks[i] = r.prompt
        batch_in = {"tokens": jnp.asarray(toks)}
        if cfg.vision_tokens:
            batch_in["vision_embeds"] = jnp.zeros(
                (B, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
        if cfg.mrope:
            pos = jnp.broadcast_to(jnp.arange(prompt_len),
                                   (B, prompt_len)).astype(jnp.int32)
            batch_in["mrope_positions"] = jnp.stack([pos] * 3, axis=1)
        if cfg.family == "encdec":
            batch_in["frames"] = jnp.zeros(
                (B, prompt_len, cfg.d_model), jnp.bfloat16)
        logits, cache = prefill(params, batch_in)
        now = time.time()
        for r in wave:
            r.t_first = now
        tok = _sample(logits, jax.random.fold_in(key, n_samples), temperature)
        n_samples += 1
        for i, r in enumerate(wave):
            r.out_tokens.append(int(tok[i]))
        # live-mask the decode loop: stop as soon as every request in the
        # wave has its tokens instead of running to a fixed step count
        while any(len(r.out_tokens) < r.max_new for r in wave):
            logits, cache = decode(params, cache, tok)
            n_decode_calls += 1
            tok = _sample(logits, jax.random.fold_in(key, n_samples),
                          temperature)
            n_samples += 1
            for i, r in enumerate(wave):
                if len(r.out_tokens) < r.max_new:
                    r.out_tokens.append(int(tok[i]))
        now = time.time()
        for r in wave:
            r.t_done = now
            r.status = "done"
            done.append(r)

    wall = time.time() - t0
    return _summary("dense", done, queue, wall, n_decode_calls,
                    temperature)


# ---------------------------------------------------------------------------
# Paged engine (block-table KV, chunked prefill, continuous batching)
# ---------------------------------------------------------------------------


def run_paged(cfg, bundle, params, queue: AdmissionQueue, *,
              batch: int, block_size: int, pool_blocks: int,
              max_context: int, prefill_chunk: int,
              temperature: float = 0.0, seed: int = 0) -> dict:
    engine = PagedEngine(bundle, params, queue, batch=batch,
                         block_size=block_size, pool_blocks=pool_blocks,
                         max_context=max_context,
                         prefill_chunk=prefill_chunk,
                         temperature=temperature, seed=seed)
    t0 = time.time()
    stats = engine.run()
    wall = time.time() - t0
    out = _summary("paged", engine.done, queue, wall,
                   stats["decode_calls"], temperature)
    out["kv"] = {k: stats[k] for k in
                 ("prefill_chunks", "oom_shed", "oom_deferrals",
                  "kv_occupancy_mean", "kv_occupancy_peak")}
    return out


def _summary(engine: str, done, queue: AdmissionQueue, wall: float,
             decode_calls: int, temperature: float) -> dict:
    total_new = sum(len(r.out_tokens) for r in done)
    ttft = (np.mean([r.t_first - r.t_submit for r in done])
            if done else 0.0)
    print(f"[serve:{engine}] {len(done)} requests, {total_new} tokens in "
          f"{wall:.2f}s  ({total_new / max(wall, 1e-9):.1f} tok/s, "
          f"mean TTFT {ttft:.2f}s, {decode_calls} decode calls, "
          f"temperature {temperature:g})", flush=True)
    if queue.shed or queue.expired:
        print(f"[serve:{engine}] degraded: {len(queue.shed)} shed, "
              f"{len(queue.expired)} expired", flush=True)
    return {"engine": engine, "requests": len(done), "tokens": total_new,
            "wall_s": wall, "tok_per_s": total_new / max(wall, 1e-9),
            "decode_calls": decode_calls, "temperature": temperature,
            "outputs": {r.rid: list(r.out_tokens) for r in done},
            "shed": [r.rid for r in queue.shed],
            "expired": [r.rid for r in queue.expired]}


def serve(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="smoke", choices=["smoke", "prod",
                                                        "multipod"])
    ap.add_argument("--engine", default="paged", choices=["paged", "dense"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="shed request submissions beyond this many "
                         "pending entries (0 = unbounded)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="expire requests that wait in the queue longer "
                         "than this before their wave starts (0 = none)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged engine: tokens per KV block")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="paged engine: total pool blocks incl. the null "
                         "block (0 = sized for --batch full sequences)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="paged engine: prompt tokens prefilled per "
                         "engine tick (0 = whole prompt at once)")
    args = ap.parse_args(argv)

    ctx = (smoke_context() if args.mesh == "smoke"
           else make_context(multi_pod=args.mesh == "multipod"))
    with mesh_context(ctx):
        cfg = get_config(args.arch, smoke=args.smoke)
        engine = args.engine
        if engine == "paged":
            ok, why = paged_supported(cfg)
            if not ok:
                print(f"[serve] paged engine unavailable for {args.arch}: "
                      f"{why} — falling back to dense", flush=True)
                engine = "dense"
        bundle = build_model(cfg)
        params = bundle.init(jax.random.PRNGKey(args.seed))

        data = SyntheticLMDataset(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=args.prompt_len,
            global_batch=args.requests, seed=args.seed))
        prompts = np.asarray(data.global_batch_at(0)["tokens"])
        queue = AdmissionQueue(max_queue=args.max_queue,
                               deadline_s=args.deadline_s)
        for i in range(args.requests):
            queue.submit(Request(rid=i, prompt=prompts[i],
                                 max_new=args.gen, t_submit=time.time()))

        if engine == "dense":
            return run_dense(cfg, bundle, params, queue, batch=args.batch,
                             prompt_len=args.prompt_len,
                             temperature=args.temperature, seed=args.seed)
        max_context = args.prompt_len + args.gen
        pool_blocks = args.pool_blocks or (
            1 + args.batch * -(-max_context // args.block_size))
        return run_paged(cfg, bundle, params, queue, batch=args.batch,
                         block_size=args.block_size,
                         pool_blocks=pool_blocks, max_context=max_context,
                         prefill_chunk=args.prefill_chunk,
                         temperature=args.temperature, seed=args.seed)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    serve()
