"""Batched serving engine: continuous batching with per-slot positions.

Fixed B decode slots; every slot carries its own position (the decode path
takes an int32 [B] index vector — cache writes are per-row scatters, masking
is per-row).  Finished sequences are immediately replaced from the request
queue; new prompts prefill *inside the running batch*: the new slot steps
through its prompt tokens while other slots keep generating — one jitted
decode program for everything, zero recompiles in steady state.

Two decode transports:

  * plain (default) — a bare ``jax.jit`` over ``model.decode_step``; the
    network is free (single host / GSPMD handles it).
  * compiled — pass ``collectives=`` a
    :class:`repro.serve.collectives.ServeCollectives`: decode runs
    rank-local under ``shard_map`` over the ``tp`` mesh with every
    per-layer all-reduce / MoE all-to-all a compiled switch program from
    the process-wide program cache.

Admission is SLO-aware when an :class:`SLOPolicy` is installed: requests
carry deadlines, the prefill-vs-decode cost of admitting is estimated
from measured tick times (falling back to the compiled prefill program's
analytic ``program_time``), and requests that cannot make their deadline
are rejected at admission instead of wasting slot ticks.

This driver is the host-side control loop; it is exercised by
tests/test_serving.py, tests/test_serve_collectives.py and
examples/serve_batched.py.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.obs import metrics as _obs

PyTree = Any


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [t] int32
    max_new_tokens: int = 16
    eos: Optional[int] = None
    # SLO deadline in seconds from submit to last token; None = best-effort
    deadline_s: Optional[float] = None
    # stamped by ServeEngine.submit (time.monotonic)
    t_submit: float = dataclasses.field(default=0.0, compare=False)


@dataclasses.dataclass
class Completion:
    rid: int
    prompt_len: int
    tokens: list[int]


@dataclasses.dataclass
class SLOPolicy:
    """Admission policy for deadline-carrying requests.

    ``decide`` returns one of

      * ``"admit"``  — take the request into the free slot
      * ``"reject"`` — it cannot make its deadline even if admitted now;
        drop it at admission (``serve.slo_rejected``) instead of burning
        decode ticks on a doomed sequence
      * ``"defer"``  — leave it queued this tick
        (``serve.admit_deferred``): too many slots are already
        prefilling, so admitting would stretch everyone's tick

    The per-tick cost estimate prefers the engine's measured tick times
    (p50 over a sliding window); before any tick has run it falls back
    to the analytic ``program_time`` of the compiled decode/prefill
    programs — the prefill-vs-decode decision the compiled path makes
    possible.

    Deadline checks run BEFORE the prefill-cap defer: a request whose
    deadline already expired (or provably cannot be met) is rejected
    even when the cap would defer it — the old order left an expired
    request parked at the queue head, silently re-deferred every tick.

    ``membership`` (a :class:`repro.elastic.Membership`) makes the
    estimate fault-aware: with ranks masked out, the compiled decode
    collectives run on a degraded fabric, so the tick estimate inflates
    by ``n_ranks / n_alive`` — deadlines that only fit a healthy fabric
    reject at admission instead of timing out mid-decode.
    """

    # admit at most this many concurrently-prefilling slots (None = no cap)
    max_concurrent_prefills: Optional[int] = None
    # safety factor on the completion-time estimate (>1 rejects earlier)
    slack: float = 1.0
    # elastic membership view; masked ranks inflate the tick estimate
    membership: Optional[Any] = None

    def _degrade_factor(self) -> float:
        m = self.membership
        if m is None:
            return 1.0
        n = getattr(m, "n_ranks", 0)
        a = getattr(m, "n_alive", n)
        if not n:
            return 1.0
        return float("inf") if a == 0 else n / a

    def decide(self, req: Request, engine: "ServeEngine",
               n_prefilling: int) -> str:
        if req.deadline_s is not None:
            waited = time.monotonic() - req.t_submit
            if waited >= req.deadline_s:
                return "reject"       # expired while queued/deferred
            tick = engine.tick_time_estimate()
            if tick is not None:
                tick = tick * self._degrade_factor()
                # in-batch prefill pays one tick per prompt token; a
                # dedicated batched prefill pass can never beat its
                # compiled program's analytic switch time, so the
                # estimate is the max of the two
                ttft = len(req.prompt) * tick
                sc = engine.collectives
                if sc is not None:
                    ttft = max(ttft, sc.prefill_comm_time(
                        engine.slots, max(len(req.prompt), 1)))
                est = waited + ttft + req.max_new_tokens * tick
                if est * self.slack > req.deadline_s:
                    return "reject"
        if self.max_concurrent_prefills is not None \
                and n_prefilling >= self.max_concurrent_prefills:
            return "defer"
        return "admit"


class ServeEngine:
    def __init__(self, model: Model, params: PyTree, *, slots: int = 4,
                 max_seq: int = 256, recorder: Optional[_obs.Recorder] = None,
                 collectives=None, admission: Optional[SLOPolicy] = None):
        self.model = model
        self.params = params
        # per-engine recorder; defaults to the process-wide one at call
        # time (so ``obs.recording()`` around a serving loop just works)
        self.recorder = recorder
        self.slots = slots
        self.max_seq = max_seq
        self.collectives = collectives
        self.admission = admission
        if collectives is None:
            self.cache = model.init_cache(slots, max_seq)
        else:
            # placed once on the TP shardings decode_fn pins, so nothing
            # moves between ticks: parameters (a no-op for parameters
            # created sharded) and the cache, created sharded so no
            # device ever holds all of it
            self.params = jax.device_put(params, collectives.shardings(
                collectives.param_specs(params)))
            make = lambda: model.init_cache(slots, max_seq)  # noqa: E731
            self.cache = jax.jit(make, out_shardings=collectives.shardings(
                collectives.cache_specs(jax.eval_shape(make))))()

        # host-side slot state
        self.rid = np.full(slots, -1, np.int64)
        self.pos = np.zeros(slots, np.int32)          # next write position
        self.remaining = np.zeros(slots, np.int32)
        self.eos = np.full(slots, -1, np.int64)
        self.prompt: list[Optional[np.ndarray]] = [None] * slots
        self.prompt_cursor = np.zeros(slots, np.int32)
        self.deadline = np.full(slots, np.inf)
        self.t_submit = np.zeros(slots)
        self.generated: list[list[int]] = [[] for _ in range(slots)]
        self.queue: collections.deque[Request] = collections.deque()
        self.done: list[Completion] = []
        self.rejected: list[Request] = []
        self.ticks = 0
        # per-tick wall times (measured; the decode host sync makes every
        # tick a natural timing boundary) -> p50/p99 gauges + admission
        self._tick_times: collections.deque[float] = collections.deque(
            maxlen=256)

        # the KV cache is persistent, step-threaded state exactly like the
        # train path's bucket arenas: donate it so every decode tick's
        # cache writes alias the previous buffers instead of allocating a
        # full cache copy per token (the engine always rebinds
        # ``self.cache`` to the returned cache, so the donated input is
        # never reused)
        if collectives is not None:
            self._decode = collectives.decode_fn(params, self.cache)
        else:
            def decode_tick(p, tok, cache, idx):
                return model.decode_step(p, tok, cache, idx)
            self._decode = jax.jit(decode_tick, donate_argnums=(2,))

    def submit(self, req: Request):
        assert len(req.prompt) + req.max_new_tokens < self.max_seq
        req.t_submit = time.monotonic()
        self.queue.append(req)

    def tick_time_estimate(self) -> Optional[float]:
        """Seconds per engine tick: measured p50 when ticks have run,
        else the compiled decode programs' analytic switch time, else
        None (plain transport, nothing measured yet)."""
        if self._tick_times:
            return float(np.median(self._tick_times))
        if self.collectives is not None:
            return self.collectives.decode_comm_time(self.slots)
        return None

    # -- slot management -------------------------------------------------------

    def _reset_slot_caches(self, slot_ids: list[int]):
        """Zero the cache rows of every slot admitted this tick in ONE
        tree traversal (a full ``jax.tree.map`` per slot was O(admits ×
        leaves) dispatches per tick)."""
        idx = jnp.asarray(np.asarray(slot_ids, np.int32))

        def reset(leaf):
            if leaf.ndim >= 1 and leaf.shape[0] == self.slots:
                fill = -1 if leaf.dtype == jnp.int32 and leaf.ndim == 2 \
                    else 0       # window 'pos' buffers use -1 = invalid
                return leaf.at[idx].set(fill)
            return leaf
        self.cache = jax.tree.map(reset, self.cache)

    def _admit(self, s: int, req: Request):
        """Host-side slot bookkeeping; the cache rows are cleared by the
        caller's batched :meth:`_reset_slot_caches`."""
        self.rid[s] = req.rid
        self.pos[s] = 0
        self.remaining[s] = req.max_new_tokens
        self.eos[s] = -1 if req.eos is None else req.eos
        self.prompt[s] = np.asarray(req.prompt, np.int32)
        self.prompt_cursor[s] = 0
        self.deadline[s] = np.inf if req.deadline_s is None else req.deadline_s
        self.t_submit[s] = req.t_submit
        self.generated[s] = []

    def _retire(self, s: int):
        self.done.append(Completion(int(self.rid[s]),
                                    len(self.prompt[s]),
                                    self.generated[s]))
        self.rid[s] = -1

    # -- one engine tick ---------------------------------------------------------

    def step(self) -> int:
        """One tick, in six phases, each an ``obs.span``: ``serve.admit``
        (admission and cache-row resets), ``serve.feed`` (the tokens and
        positions to the device), ``serve.dispatch`` (the decode call
        returning), ``serve.device_wait``, ``serve.logits_pull`` and
        ``serve.sample``.  ``serve.decode_s`` spans dispatch, wait and
        pull."""
        rec = self.recorder if self.recorder is not None else _obs.RECORDER
        rec.count("serve.ticks")
        rec.gauge("serve.queue_depth", len(self.queue))
        with _obs.span("serve.admit", rec):
            self._admit_queued(rec)
        active = np.flatnonzero(self.rid >= 0)
        rec.gauge("serve.active", int(active.size))
        if active.size == 0:
            return 0

        with _obs.span("serve.feed", rec):
            # token each active slot feeds this tick: next prompt token
            # while prefilling, else its last generated token
            tok = np.zeros(self.slots, np.int32)
            in_prefill = np.zeros(self.slots, bool)
            for s in active:
                cur = self.prompt_cursor[s]
                if cur < len(self.prompt[s]):
                    tok[s] = self.prompt[s][cur]
                    in_prefill[s] = True
                else:
                    tok[s] = self.generated[s][-1] if self.generated[s] \
                        else self.prompt[s][-1]
            tok, idx = jnp.asarray(tok), jnp.asarray(self.pos)

        t0 = time.perf_counter()
        with _obs.span("serve.dispatch", rec):
            lg, self.cache = self._decode(self.params, tok, self.cache, idx)
        # the tick's ONE host sync: greedy sampling below needs the logits
        # on the host whether or not recording is on — the wait is made
        # explicit so that the pull after it times the transfer alone
        with _obs.span("serve.device_wait", rec):
            jax.block_until_ready(lg)
        with _obs.span("serve.logits_pull", rec):
            lg = np.asarray(lg)
        dt = time.perf_counter() - t0
        self._tick_times.append(dt)
        if rec.enabled:
            rec.count("serve.logits_bytes", lg.nbytes)
            rec.observe("serve.decode_s", dt)
            order = sorted(self._tick_times)
            rec.gauge("serve.decode_p50_s", order[len(order) // 2])
            rec.gauge("serve.decode_p99_s",
                      order[min(len(order) - 1, int(len(order) * 0.99))])
            live = self.deadline[active]
            if np.isfinite(live).any():
                now = time.monotonic()
                headroom = (live - (now - self.t_submit[active]))
                rec.gauge("serve.deadline_headroom_s",
                          float(headroom[np.isfinite(live)].min()))
        self.ticks += 1

        with _obs.span("serve.sample", rec):
            retired = 0
            for s in active:
                self.pos[s] += 1
                if in_prefill[s]:
                    self.prompt_cursor[s] += 1
                    if self.prompt_cursor[s] < len(self.prompt[s]):
                        continue               # still prefilling
                    # prompt finished: this tick's logits predict token 1
                nxt = int(lg[s].argmax())
                self.generated[s].append(nxt)
                self.remaining[s] -= 1
                if (self.remaining[s] <= 0 or nxt == self.eos[s]
                        or self.pos[s] >= self.max_seq - 1):
                    self._retire(s)
                    retired += 1
            if retired:
                rec.count("serve.retired", retired)
        return int(active.size)

    def _admit_queued(self, rec) -> None:
        """Fill free slots from the queue (through the admission policy
        when one is installed) and clear the admitted slots' cache rows."""
        admitted_slots: list[int] = []
        n_prefilling = sum(
            1 for s in range(self.slots)
            if self.rid[s] >= 0
            and self.prompt_cursor[s] < len(self.prompt[s]))
        deferred = False
        for s in range(self.slots):
            if self.rid[s] >= 0 or deferred:
                continue
            while self.queue:
                req = self.queue[0]
                verdict = "admit" if self.admission is None else \
                    self.admission.decide(req, self, n_prefilling)
                if verdict == "reject":
                    self.queue.popleft()
                    self.rejected.append(req)
                    rec.count("serve.slo_rejected")
                    continue
                if verdict == "defer":
                    rec.count("serve.admit_deferred")
                    deferred = True
                    break
                self.queue.popleft()
                self._admit(s, req)
                admitted_slots.append(s)
                n_prefilling += 1
                break
        if admitted_slots:
            self._reset_slot_caches(admitted_slots)
            rec.count("serve.admitted", len(admitted_slots))

    def run_to_completion(self, max_ticks: int = 100000) -> list[Completion]:
        for _ in range(max_ticks):
            if self.step() == 0 and not self.queue:
                break
        return sorted(self.done, key=lambda c: c.rid)
