"""Batched serving driver: continuous-batching-lite over the cached decode
path (prefill + per-token decode with slot reuse).

A RequestQueue of prompts is served by a fixed-width slot table: finished
sequences release their slot to the next queued request mid-flight; the
decode step always runs the full (padded) batch, which is exactly how the
production decode shapes (decode_32k / long_500k) are lowered.
"""
from __future__ import annotations

import argparse
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, REDUCED
from repro.models import backbone as bb
from repro.models.modality import synthetic_prefix


@dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (P,) int32
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False


class ServeResult(Dict[int, List[int]]):
    """{rid: tokens} plus `.status`: {rid: done|truncated|pending}.

    `serve()` stops at `max_steps` whether or not every request finished;
    without per-request status a half-decoded request was indistinguishable
    from a finished one. "done" reached `max_new`, "truncated" was admitted
    and emitted tokens but got cut off, "pending" never reached a slot.
    """

    def __init__(self, outputs: Dict[int, List[int]],
                 status: Dict[int, str]):
        super().__init__(outputs)
        self.status = status


class BatchedServer:
    """Slot-table continuous batching over decode_step."""

    def __init__(self, cfg, params, *, slots: int = 4, cache_len: int = 512,
                 temperature: float = 0.0, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self.state = bb.init_decode_state(cfg, slots, cache_len, jnp.float32)
        self.pos = jnp.zeros((slots,), jnp.int32)
        self.cur_tok = jnp.zeros((slots, 1), jnp.int32)
        self.active: List[Optional[Request]] = [None] * slots
        self._decode = jax.jit(
            lambda p, s, t, c: bb.decode_step(p, s, t, c, cfg,
                                              compute_dtype=jnp.float32))

    def _prefill_slot(self, slot: int, req: Request):
        # per-slot prefill on a B=1 slice of the slot's cache (every decode
        # state leaf carries batch at axis 1): the prompt decodes as P
        # single-sequence steps instead of P full-batch steps, and live
        # slots' state is untouched by construction — admission cost no
        # longer scales with the slot count. Batched prefill stays the
        # prefill_32k path.
        toks = req.prompt
        self.pos = self.pos.at[slot].set(len(toks))
        if len(toks) == 0:
            # empty prompt: nothing to prefill (and no logits to sample
            # from) — seed the slot with token 0 at pos 0 and let the next
            # batched decode step produce the first output token
            self.cur_tok = self.cur_tok.at[slot, 0].set(0)
            return
        sub = jax.tree.map(lambda a: a[:, slot:slot + 1], self.state)
        tok = jnp.zeros((1, 1), jnp.int32)
        pos = jnp.zeros((1,), jnp.int32)
        for i, t in enumerate(toks):
            tok = tok.at[0, 0].set(int(t))
            pos = pos.at[0].set(i)
            logits, sub = self._decode(self.params, sub, tok, pos)
        self.state = jax.tree.map(
            lambda full, s: full.at[:, slot:slot + 1].set(s),
            self.state, sub)
        nxt = self._sample(logits[0, 0], req)
        req.out.append(int(nxt))
        self.cur_tok = self.cur_tok.at[slot, 0].set(int(nxt))

    def _sample(self, logits: jnp.ndarray, req: Request) -> int:
        if self.temperature <= 0:
            return int(jnp.argmax(logits))
        # per-request stream: the key depends only on (rid, #tokens emitted
        # so far), never on which slot the request landed in or what its
        # batch-mates were doing — temperature>0 output is reproducible
        # across admission orders and slot layouts (a split-per-sample
        # self.key made every sample depend on global serve history)
        k = jax.random.fold_in(jax.random.fold_in(self.key, req.rid),
                               len(req.out))
        return int(jax.random.categorical(k, logits / self.temperature))

    def serve(self, requests: List[Request], *, max_steps: int = 10_000
              ) -> ServeResult:
        queue = deque(requests)        # FIFO: O(1) popleft, not list.pop(0)
        steps = 0
        while (any(self.active) or queue) and steps < max_steps:
            # admit
            for s in range(self.slots):
                if self.active[s] is None and queue:
                    req = queue.popleft()
                    self.active[s] = req
                    self._prefill_slot(s, req)
            if not any(self.active):
                break
            # one batched decode step; only LIVE slots advance their
            # position — an always-advancing pos silently marched idle
            # slots past cache_len (clamped/dropped cache writes under
            # jit) and kept released slots decoding stale tokens
            live = jnp.asarray([0 if r is None else 1 for r in self.active],
                               jnp.int32)
            logits, self.state = self._decode(self.params, self.state,
                                              self.cur_tok, self.pos)
            self.pos = self.pos + live
            steps += 1
            new_toks = self.cur_tok
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                nxt = self._sample(logits[s, 0], req)
                req.out.append(nxt)
                new_toks = new_toks.at[s, 0].set(nxt)
                if len(req.out) >= req.max_new:
                    req.done = True
                    self.active[s] = None      # release slot mid-flight...
                    self.pos = self.pos.at[s].set(0)       # ...and reset it
                    new_toks = new_toks.at[s, 0].set(0)
            self.cur_tok = new_toks
        status = {r.rid: ("done" if r.done
                          else "truncated" if r.out else "pending")
                  for r in requests}
        return ServeResult({r.rid: r.out for r in requests}, status)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=sorted(ARCHS))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = REDUCED[args.arch]
    key = jax.random.PRNGKey(args.seed)
    params = bb.init_params(cfg, key, jnp.float32)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12)),
                    max_new=args.max_new)
            for i in range(args.requests)]
    server = BatchedServer(cfg, params, slots=args.slots, cache_len=256)
    t0 = time.perf_counter()
    outs = server.serve(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(v) for v in outs.values())
    print(f"served {len(reqs)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s, slots={args.slots})")
    for rid, toks in sorted(outs.items()):
        print(f"  req {rid}: {len(toks)} tokens -> {toks[:8]}...")


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    main()
