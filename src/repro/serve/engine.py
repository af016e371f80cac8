"""Batched serving engine: continuous batching over a paged KV-cache pool.

Slots: a fixed decode batch of ``n_slots`` sequences with per-slot positions
(models/attention.py vector-pos path). Requests queue up; a finished slot is
immediately refilled from the queue — decode never stalls on stragglers of
the batch.

Two cache layouts:

* **paged** (dense/moe/vlm, window=0; the default for those families): one
  pool of fixed-size KV pages plus a per-slot int32 page table
  (models/attention.py paged layout). A whole admission wave prefills in ONE
  batched forward pass (``lm_paged_prefill``) scattered straight into pages;
  pages free on retire and are reused. Per-tick bookkeeping (``pos``,
  ``cur``, the active mask) lives on device — each tick is one jitted call
  plus a single host sync that fetches the sampled tokens and positions.
* **dense** (fallback for ssm/hybrid/encdec and sliding-window configs, or
  ``paged=False``): the per-slot (B, Kh, S, hd) cache with one-request-at-a-
  time prefill (full-sequence forward for attention families, sequential
  decode replay otherwise).

Dense and paged layouts are numerically identical (the paged read gathers a
slot's pages in logical order and masks exactly like the dense path); tests
pin the equivalence. Sampling: greedy or temperature. All steps are jit'd
once per shape bucket (admission pads prompts to power-of-two page
multiples, so a serving session compiles a handful of prefill shapes, not
one per prompt length).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tf
from repro.models.model import ModelApi
from repro.spans import span

_PAGED_FAMILIES = ("dense", "moe", "vlm")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new: int = 32
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


def _axes_leaf(x) -> bool:
    """A cache_spec leaf: a tuple of logical axis names / None."""
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


class ServeEngine:
    def __init__(self, api: ModelApi, params, *, n_slots: int = 4,
                 max_seq: int = 512, temperature: float = 0.0,
                 eos_id: Optional[int] = None, seed: int = 0,
                 paged: Optional[bool] = None, page_size: int = 16,
                 n_pages: Optional[int] = None):
        self.api = api
        self.cfg = api.cfg
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.temperature = temperature
        self.eos_id = eos_id
        self._rng = jax.random.PRNGKey(seed)
        self.queue: deque[Request] = deque()
        self.slot_req: list[Optional[Request]] = [None] * n_slots
        self._next_uid = 1000            # monotonic: uids never reused
        self._completed: list[Request] = []
        self.stats: dict[str, Any] = {
            "prefill_tokens": 0, "decode_tokens": 0, "prefill_calls": 0,
            "ticks": 0, "wall_s": 0.0, "occupancy_sum": 0.0,
            "occupancy_n": 0}

        pageable = self.cfg.family in _PAGED_FAMILIES and not self.cfg.window
        if paged is None:
            paged = pageable
        elif paged and not pageable:
            raise ValueError(
                f"paged serving needs an attention KV cache without a "
                f"sliding window (family={self.cfg.family!r}, "
                f"window={self.cfg.window})")
        self.paged = paged

        self.pos = jnp.zeros((n_slots,), jnp.int32)
        self.cur = jnp.zeros((n_slots, 1), jnp.int32)
        self.active = np.zeros((n_slots,), bool)
        self._active_dev = jnp.asarray(self.active)

        if paged:
            if max_seq % page_size:
                raise ValueError(f"max_seq={max_seq} must be a multiple of "
                                 f"page_size={page_size}")
            self.page_size = page_size
            self.max_pages = max_seq // page_size
            self.n_pages = (n_slots * self.max_pages if n_pages is None
                            else n_pages)
            if self.n_pages < self.max_pages:
                raise ValueError("page pool smaller than one request's "
                                 f"worst case ({self.max_pages} pages)")
            self._trash = self.n_pages   # pool page P: scatter sink, never read
            self.cache = tf.lm_paged_decode_init(
                params, self.cfg, self.n_pages + 1, page_size)
            self._table_np = np.full((n_slots, self.max_pages), self._trash,
                                     np.int32)
            self.page_table = jnp.asarray(self._table_np)
            self._free: list[int] = list(range(self.n_pages))
            self._slot_pages: list[list[int]] = [[] for _ in range(n_slots)]
            self._stalled = np.zeros((n_slots,), bool)
            self._prefill_raw, self._tick_raw = _make_paged_fns(
                self.cfg, temperature)
            self._prefill_jit = jax.jit(self._prefill_raw)
            self._tick_jit = jax.jit(self._tick_raw)
            self._last_wave = None
        else:
            self._decode = jax.jit(
                lambda p, c, t, pos: api.decode_step(p, c, t, pos))
            if self.cfg.family in _PAGED_FAMILIES:
                self._prefill1 = jax.jit(
                    lambda p, b: tf.lm_prefill(p, self.cfg, b, max_seq))
            else:
                self._prefill1 = None
            self.cache = api.decode_init(
                params, {"tokens": jnp.zeros((n_slots, 1), jnp.int32),
                         "max_seq": max_seq})

    # ------------------------------------------------------------------
    def submit(self, prompt: list[int], *, max_new: int = 32) -> Request:
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.max_seq - 1:
            raise ValueError(f"prompt length {len(prompt)} exceeds "
                             f"max_seq-1={self.max_seq - 1}")
        req = Request(uid=self._next_uid, prompt=prompt, max_new=max_new)
        self._next_uid += 1
        self.queue.append(req)
        return req

    # -- paged path ----------------------------------------------------
    def _bucket(self, sp: int) -> int:
        """Pad a prompt length to a power-of-two multiple of the page size
        (capped at max_seq) — bounds the number of prefill compilations."""
        n = self.page_size
        while n < sp:
            n *= 2
        return min(n, self.max_seq)

    def _admit_pages(self, sp: int, spad: int) -> int:
        """Pages a ``sp``-token prompt takes at admission in a wave padded
        to ``spad``: those the padded prefill writes, and the page of
        position ``sp``, which the first tick writes — one more than the
        bucket's when the prompt fills it exactly."""
        return max(spad // self.page_size, sp // self.page_size + 1)

    def _set_active(self, slot: int, value: bool) -> None:
        self.active[slot] = value
        self._active_dev = jnp.asarray(self.active)

    def _next_key(self):
        if self.temperature <= 0:
            return self._rng
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _resume_stalled(self) -> None:
        """Re-activate slots that stalled on an empty free list once pages
        are available again (their whole state — pages, pos, cur — is
        intact, so generation just continues)."""
        resumed = False
        for slot in range(self.n_slots):
            if not self._stalled[slot]:
                continue
            if not self._free:
                break       # NOT return: already-resumed slots need the sync
            pp = len(self._slot_pages[slot])
            pid = self._free.pop()
            self._slot_pages[slot].append(pid)
            self._table_np[slot, pp] = pid
            self._stalled[slot] = False
            self._set_active(slot, True)
            resumed = True
        if resumed:
            self.page_table = jnp.asarray(self._table_np)

    def _admit_wave(self) -> bool:
        """Admit up to ``n_slots`` queued requests in ONE batched prefill:
        pad the wave's prompts to a common bucketed length, allocate the
        covering pages per member, run ``lm_paged_prefill`` (forward +
        scatter into pages) once, and sample each member's first token."""
        free_slots = [s for s in range(self.n_slots)
                      if self.slot_req[s] is None]
        wave: list[tuple[int, Request]] = []
        while free_slots and self.queue:
            cand = [r for _, r in wave] + [self.queue[0]]
            spad = self._bucket(max(len(r.prompt) for r in cand))
            need = sum(self._admit_pages(len(r.prompt), spad) for r in cand)
            if need > len(self._free):
                break
            wave.append((free_slots.pop(0), self.queue.popleft()))
        if not wave:
            return False

        spad = self._bucket(max(len(r.prompt) for _, r in wave))
        npp = spad // self.page_size
        toks = np.zeros((self.n_slots, spad), np.int32)
        rows = np.full((self.n_slots, npp), self._trash, np.int32)
        lens = np.ones((self.n_slots,), np.int32)
        adm = np.zeros((self.n_slots,), bool)
        for slot, req in wave:
            sp = len(req.prompt)
            toks[slot, :sp] = req.prompt
            pages = [self._free.pop()
                     for _ in range(self._admit_pages(sp, spad))]
            self._slot_pages[slot] = pages
            self._table_np[slot, :] = self._trash
            self._table_np[slot, :len(pages)] = pages
            rows[slot] = pages[:npp]
            lens[slot] = sp
            adm[slot] = True
        self.page_table = jnp.asarray(self._table_np)

        wave_args = tuple(jnp.asarray(a) for a in (toks, rows, lens, adm))
        self._last_wave = wave_args
        with span("serve.admit", requests=len(wave), bucket=spad):
            self.cache, self.pos, self.cur, nxt = self._prefill_jit(
                self.params, self.cache, *wave_args, self.pos, self.cur,
                self._next_key())
            nxt_h = np.asarray(jax.device_get(nxt))
        for slot, req in wave:
            req.out.append(int(nxt_h[slot]))
            self.slot_req[slot] = req
            self._set_active(slot, True)
            self.stats["prefill_tokens"] += len(req.prompt)
        self.stats["prefill_calls"] += 1
        return True

    def _step_paged(self) -> None:
        self._resume_stalled()
        self._admit_wave()
        if not self.active.any():
            if any(r is not None for r in self.slot_req):
                raise RuntimeError(
                    "page pool exhausted: every in-flight request is "
                    "stalled and nothing can retire — size the pool at "
                    "n_slots * (max_seq // page_size) pages to rule this "
                    "out")
            return
        with span("serve.tick", active=int(self.active.sum())):
            self.cache, self.cur, self.pos, nxt, _logits = self._tick_jit(
                self.params, self.cache, self.cur, self.pos,
                self._active_dev, self.page_table, self._next_key())
            # the tick's single host sync: sampled tokens + updated positions
            nxt_h, pos_h = (np.asarray(a)
                            for a in jax.device_get((nxt, self.pos)))
        self.stats["ticks"] += 1
        self.stats["decode_tokens"] += int(self.active.sum())
        self.stats["occupancy_sum"] += self.pool_occupancy()
        self.stats["occupancy_n"] += 1
        table_dirty = False
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None or not self.active[slot]:
                continue
            tok = int(nxt_h[slot])
            req.out.append(tok)
            if ((self.eos_id is not None and tok == self.eos_id)
                    or len(req.out) >= req.max_new
                    or int(pos_h[slot]) >= self.max_seq - 1):
                self._retire(slot)
                table_dirty = True
                continue
            pp = int(pos_h[slot]) // self.page_size   # next write position
            if pp >= len(self._slot_pages[slot]):
                if self._free:
                    pid = self._free.pop()
                    self._slot_pages[slot].append(pid)
                    self._table_np[slot, pp] = pid
                    table_dirty = True
                else:
                    self._stalled[slot] = True
                    self._set_active(slot, False)
        if table_dirty:
            self.page_table = jnp.asarray(self._table_np)

    def pool_occupancy(self) -> float:
        """Fraction of the page pool currently assigned to slots (paged);
        fraction of cache slots active (dense)."""
        if self.paged:
            return 1.0 - len(self._free) / self.n_pages
        return float(self.active.mean())

    # -- dense path ----------------------------------------------------
    def _scatter_slot(self, big, small, slot: int):
        """Scatter a single-request cache into the batched cache along each
        leaf's DECLARED batch axis (``cache_spec`` logical names) — leaves
        without a "cache_batch" axis (e.g. a ring cache's shared ``kpos``)
        are left untouched instead of being corrupted by a positional
        guess."""
        big_leaves, treedef = jax.tree.flatten(big)
        small_leaves = jax.tree.leaves(small)
        spec_leaves = jax.tree.leaves(self.api.cache_spec(),
                                      is_leaf=_axes_leaf)
        out = []
        for b, s, axes in zip(big_leaves, small_leaves, spec_leaves):
            if _axes_leaf(axes) and "cache_batch" in axes:
                ax = axes.index("cache_batch")
                idx = tuple(slice(slot, slot + 1) if i == ax else slice(None)
                            for i in range(b.ndim))
                out.append(b.at[idx].set(s))
            else:
                out.append(b)
        return jax.tree.unflatten(treedef, out)

    def _admit(self, slot: int, req: Request) -> None:
        """Prefill ``req`` into ``slot``'s cache region (dense layout)."""
        prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]     # (1, Sp)
        sp = prompt.shape[1]
        if self._prefill1 is not None:
            logits, cache1 = self._prefill1(self.params,
                                            {"tokens": prompt})
            self.cache = self._scatter_slot(self.cache, cache1, slot)
        else:
            # sequential prefill: replay prompt tokens through decode_step on
            # a fresh single-slot cache, then scatter.
            c1 = self.api.decode_init(
                self.params, {"tokens": prompt[:, :1],
                              "max_seq": self.max_seq})
            logits = None
            for i in range(sp):
                logits, c1 = self._decode(self.params, c1, prompt[:, i:i + 1],
                                          jnp.int32(i))
            self.cache = self._scatter_slot(self.cache, c1, slot)
        next_tok = jnp.argmax(logits[0, -1]).astype(jnp.int32)
        self.pos = self.pos.at[slot].set(sp)
        self.cur = self.cur.at[slot, 0].set(next_tok)
        req.out.append(int(next_tok))
        self._set_active(slot, True)
        self.slot_req[slot] = req
        self.stats["prefill_tokens"] += sp
        self.stats["prefill_calls"] += 1

    def _step_dense(self) -> None:
        self.admit()
        if not self.active.any():
            return
        logits, self.cache = self._decode(self.params, self.cache, self.cur,
                                          self.pos)
        nxt = self._sample(logits[:, -1, :])                     # (B,)
        self.pos = self.pos + jnp.asarray(self.active, jnp.int32)
        self.cur = nxt[:, None]
        self.stats["ticks"] += 1
        self.stats["decode_tokens"] += int(self.active.sum())
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None:
                continue
            tok = int(nxt[slot])
            req.out.append(tok)
            if ((self.eos_id is not None and tok == self.eos_id)
                    or len(req.out) >= req.max_new
                    or int(self.pos[slot]) >= self.max_seq - 1):
                self._retire(slot)

    # ------------------------------------------------------------------
    def _retire(self, slot: int) -> None:
        req = self.slot_req[slot]
        if req is not None:
            req.done = True
            self._completed.append(req)
        self.slot_req[slot] = None
        self._set_active(slot, False)
        if self.paged:
            self._free.extend(self._slot_pages[slot])
            self._slot_pages[slot] = []
            self._table_np[slot, :] = self._trash
            self._stalled[slot] = False

    def _sample(self, logits) -> jax.Array:
        if self.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self._rng, sub = jax.random.split(self._rng)
        return jax.random.categorical(
            sub, logits / self.temperature, axis=-1).astype(jnp.int32)

    # ------------------------------------------------------------------
    def admit(self) -> bool:
        """Admit queued requests into free slots without a decode tick
        (paged: one batched wave); True where any was admitted."""
        if self.paged:
            return self._admit_wave()
        admitted = False
        for slot in range(self.n_slots):
            if not self.active[slot] and self.queue:
                self._admit(slot, self.queue.popleft())
                admitted = True
        return admitted

    def step(self) -> None:
        """One engine tick: admit into free slots, then one decode step."""
        if self.paged:
            self._step_paged()
        else:
            self._step_dense()

    def run(self, *, max_ticks: int = 1000) -> list[Request]:
        """Tick until the queue drains; returns every request completed
        since the last ``run`` call — including requests submitted after a
        previous tick and requests finished via manual ``step()`` calls
        (completions are derived from all requests seen, not a snapshot)."""
        t0 = time.perf_counter()
        for _ in range(max_ticks):
            if not self.queue and all(r is None for r in self.slot_req):
                break
            self.step()
        self.stats["wall_s"] += time.perf_counter() - t0
        done, self._completed = self._completed, []
        return done

    def report(self) -> dict:
        """Throughput / occupancy summary over the ``run`` calls so far."""
        s = self.stats
        wall = s["wall_s"] or 1e-9
        occ = (s["occupancy_sum"] / s["occupancy_n"]
               if s["occupancy_n"] else self.pool_occupancy())
        return {"paged": self.paged,
                "decode_tok_s": s["decode_tokens"] / wall,
                "total_tok_s": (s["decode_tokens"] + s["prefill_tokens"])
                / wall,
                "prefill_tokens": s["prefill_tokens"],
                "decode_tokens": s["decode_tokens"],
                "prefill_calls": s["prefill_calls"],
                "ticks": s["ticks"], "wall_s": s["wall_s"],
                "mean_pool_occupancy": occ}

    # -- probe integration ---------------------------------------------
    def probe_cells(self):
        """Snapshot the engine's prefill and decode ticks as pure,
        re-runnable cells (launch/steps.py-style: a fn plus concrete args):
        ``(prefill_fn, prefill_args, tick_fn, tick_args)``. The serve
        RegionTargets (serve/load.py) wrap these with graph-level noise —
        re-running a cell recomputes the same state transition, so sweeps
        can time it any number of times."""
        if not self.paged:
            raise RuntimeError("probe_cells needs the paged engine")
        if self._last_wave is None:
            raise RuntimeError("admit at least one wave before probing")
        pf_args = (self.params, self.cache, *self._last_wave, self.pos,
                   self.cur, self._rng)
        tk_args = (self.params, self.cache, self.cur, self.pos,
                   self._active_dev, self.page_table, self._rng)
        return self._prefill_raw, pf_args, self._tick_raw, tk_args


def _make_paged_fns(cfg, temperature: float):
    """The paged engine's two pure device programs (jitted once each).

    prefill(params, cache, toks, rows, lens, adm, pos, cur, key)
        -> (cache, pos, cur, next_tokens)
    tick(params, cache, cur, pos, active, table, key)
        -> (cache, cur, pos, next_tokens, logits)

    The tick's logits are the step's float32 logits, (slots, vocab); the
    engine samples from them and drops them, a probe compares them.
    """
    def sample(logits, key):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)

    def prefill(p, cache, toks, rows, lens, adm, pos, cur, key):
        logits, cache = tf.lm_paged_prefill(p, cfg, {"tokens": toks}, cache,
                                            rows)
        last = jnp.take_along_axis(
            logits, (lens - 1)[:, None, None], axis=1)[:, 0]     # (B, V)
        nxt = sample(last, key)
        pos = jnp.where(adm, lens, pos)
        cur = jnp.where(adm[:, None], nxt[:, None], cur)
        return cache, pos, cur, nxt

    def tick(p, cache, cur, pos, active, table, key):
        logits, cache = tf.lm_paged_decode_step(p, cfg, cache, cur, pos,
                                                table)
        nxt = sample(logits[:, -1, :], key)
        pos = pos + active.astype(jnp.int32)
        cur = jnp.where(active[:, None], nxt[:, None], cur)
        return cache, cur, pos, nxt, logits[:, -1, :].astype(jnp.float32)

    return prefill, tick
