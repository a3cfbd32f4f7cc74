"""Continuous-batching inference engine with step-boundary preemption.

The engine rung of the truth ladder replays frames through it. Lanes hold
per-request recurrent-state slots inside one batched cache (a per-lane
``len`` and a ``state`` row); ``decode_tick`` advances every active lane
with a single jitted decode step. LCFSP preemption frees a lane between
steps; the scheduler (repro.serving.scheduler) decides when.

A "frame analysis" request = prefill(frame tokens) + ``decode_tokens``
decode steps (the recognition head of the paper's detection task mapped to
autoregressive analysis output). The model is
:class:`NullAnalyticsModel`, a stub whose cost is negligible: the rung's
timing comes from sampled service draws, not model FLOPs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .scheduler import Frame

FREE, DECODING = 0, 2


@dataclasses.dataclass
class LaneState:
    status: int = FREE
    stream_id: int = -1
    frame: Optional[Frame] = None
    remaining: int = 0


@dataclasses.dataclass
class Result:
    stream_id: int
    frame: Frame
    tokens: np.ndarray
    t_done: float = 0.0


class Engine:
    def __init__(self, model, params, n_lanes: int = 8, max_len: int = 256,
                 decode_tokens: int = 8):
        self.model = model
        self.params = params
        self.n_lanes = n_lanes
        self.max_len = max_len
        self.decode_tokens = decode_tokens
        self.cache = model.init_cache(n_lanes, max_len)
        self.lanes: List[LaneState] = [LaneState() for _ in range(n_lanes)]
        # Fused admit: prefill + lane insert + first-token argmax in ONE
        # dispatch (dynamic lane index), over a memoized single-lane
        # cache — a fresh cache per admit dominated replay-plane runtime.
        self._single_cache = model.init_cache(1, max_len)

        def _admit_fused(p, tokens, batched, single, lane):
            logits, single = model.prefill(p, {"tokens": tokens}, single)

            def ins(b, s):
                if (b.ndim == s.ndim and b.shape[0] == s.shape[0]
                        and b.ndim >= 2):
                    return b.at[:, lane].set(s[:, 0])
                return b.at[lane].set(s[0])
            return jnp.argmax(logits[0, -1]), jax.tree.map(
                ins, batched, single)

        self._admit_fused = jax.jit(_admit_fused)

        def _decode_next(p, t, c):
            logits, c = model.decode_step(p, t, c)
            return jnp.argmax(logits, axis=-1), c

        self._decode_next = jax.jit(_decode_next)
        self._steps = 0

    # ------------------------------------------------------------------
    def free_lanes(self) -> List[int]:
        return [i for i, l in enumerate(self.lanes) if l.status == FREE]

    def preempt_stream(self, stream_id: int) -> int:
        """Abort any in-flight lane of this stream (LCFSP). Returns count."""
        n = 0
        for lane in self.lanes:
            if lane.status != FREE and lane.stream_id == stream_id:
                self._release(lane)
                n += 1
        return n

    def _release(self, lane: LaneState) -> None:
        """Return a lane to the free pool with no stale bookkeeping: a
        freed-but-dirty lane (leftover ``remaining``/``out``/``stream_id``
        from a churned-out stream) must not leak into the next admit or
        show up as busy in ``utilization``."""
        lane.status = FREE
        lane.stream_id = -1
        lane.frame = None
        lane.remaining = 0
        lane.out = []

    def admit(self, frame: Frame, tokens: np.ndarray,
              lane: Optional[int] = None) -> bool:
        """Prefill a frame into a free lane. tokens: int32 [seq].

        ``lane`` pins the request to a specific free lane (the engine
        replay plane keeps one lane per stream); default picks the first
        free lane. Returns False when no (or the pinned) lane is busy."""
        if lane is None:
            free = self.free_lanes()
            if not free:
                return False
            lane = free[0]
        elif self.lanes[lane].status != FREE:
            return False
        first, self.cache = self._admit_fused(
            self.params, jnp.asarray(tokens, jnp.int32)[None],
            self.cache, self._single_cache, lane)
        st = self.lanes[lane]
        st.status = DECODING
        st.stream_id = frame.stream_id
        st.frame = frame
        st.remaining = self.decode_tokens
        st.out = [int(first)]
        return True

    def decode_tick(self) -> List[Result]:
        """One batched decode step across all lanes; returns completions."""
        active = [i for i, l in enumerate(self.lanes) if l.status ==
                  DECODING]
        if not active:
            return []
        last = np.zeros((self.n_lanes,), np.int32)
        for i in active:
            last[i] = self.lanes[i].out[-1]
        nxt, self.cache = self._decode_next(self.params,
                                            jnp.asarray(last), self.cache)
        nxt = np.asarray(nxt)
        self._steps += 1
        done = []
        for i in active:
            lane = self.lanes[i]
            lane.out.append(int(nxt[i]))
            lane.remaining -= 1
            if lane.remaining <= 0:
                done.append(Result(lane.stream_id, lane.frame,
                                   np.asarray(lane.out)))
                self._release(lane)
        return done

    @property
    def utilization(self) -> float:
        busy = sum(1 for l in self.lanes if l.status != FREE)
        return busy / self.n_lanes


# ---------------------------------------------------------------------------
# Replay stub model
# ---------------------------------------------------------------------------

class NullAnalyticsModel:
    """Tiny deterministic recognition head for engine-rung replay.

    The truth-ladder engine rung needs the *batching/lane mechanics* of a
    real continuous-batching engine — admit/prefill/decode_tick/preempt —
    at suite scale, where timing comes from sampled service draws, not
    model FLOPs. This stub satisfies the model protocol (``init`` /
    ``init_cache`` / ``prefill`` / ``decode_step``) with a cumsum-embed
    recurrent cell small enough that thousands of frames cost
    milliseconds, while staying fully deterministic under a fixed key.
    """

    def __init__(self, d: int = 8, vocab: int = 32):
        self.d = d
        self.vocab = vocab

    def init(self, key):
        """Parameters: ``emb`` [vocab, d] ~ N(0, 1) and ``out`` [d, vocab]
        ~ N(0, 1/d), drawn from the two halves of ``key``."""
        k_emb, k_out = jax.random.split(key, 2)
        return {"emb": jax.random.normal(k_emb, (self.vocab, self.d),
                                         jnp.float32),
                "out": jax.random.normal(k_out, (self.d, self.vocab),
                                         jnp.float32)
                * (1.0 / math.sqrt(self.d))}

    def init_cache(self, lanes: int, max_len: int):
        """Empty cache for ``lanes`` lanes. The leading extent-1 dim on
        ``state`` takes the admit's stacked ([1, lanes, d]) insert path;
        ``len`` takes the flat [lanes] one."""
        return {"len": jnp.zeros((lanes,), jnp.int32),
                "state": jnp.zeros((1, lanes, self.d), jnp.float32)}

    def prefill(self, params, batch, cache):
        tok = batch["tokens"]                       # [B, S]
        emb = params["emb"][tok]                    # [B, S, d]
        states = jnp.tanh(jnp.cumsum(emb, axis=1))  # [B, S, d]
        logits = states @ params["out"]             # [B, S, V]
        cache = {"len": jnp.full_like(cache["len"], tok.shape[1]),
                 "state": jnp.swapaxes(states[:, -1:], 0, 1)}
        return logits, cache

    def decode_step(self, params, tokens, cache):
        emb = params["emb"][tokens]                 # [lanes, d]
        state = jnp.tanh(cache["state"][0] + emb)
        logits = state @ params["out"]              # [lanes, V]
        cache = {"len": cache["len"] + 1, "state": state[None]}
        return logits, cache


def make_replay_engine(n_lanes: int, *, max_len: int = 64,
                       decode_tokens: int = 4, seed: int = 0) -> Engine:
    """Engine over :class:`NullAnalyticsModel` for the replay plane —
    deterministic under ``seed``, one lane per replayed stream."""
    model = NullAnalyticsModel()
    return Engine(model, model.init(jax.random.PRNGKey(seed)),
                  n_lanes=n_lanes, max_len=max_len,
                  decode_tokens=decode_tokens)
