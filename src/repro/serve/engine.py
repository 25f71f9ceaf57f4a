"""Continuous-batching inference engine (the MTC-TRE payload).

Slot-based KV/SSM cache: ``max_batch`` slots of capacity ``max_len``.
Requests are admitted into free slots (prefill writes the slot), then all
active slots decode together each step; finished slots free immediately so
new requests join mid-flight — continuous batching. Greedy sampling.

The admit path is batched (:meth:`Engine.admit_many`): requests admitted
together are grouped by prompt shape and prefilled in one forward pass per
group, then spliced into their slots — a trace-rate driver that buffers a
tick's launches gets one prefill dispatch per prompt length instead of one
per request. Slot accounting (last-token gather, output accumulation,
length bumps, finish detection) is vectorized over NumPy slot arrays; the
only per-request Python is materializing finished requests.

The jitted programs are named ``engine_decode`` and ``engine_prefill``
(``jit_engine_decode`` and ``jit_engine_prefill`` in a profiler trace and
in ``repro.obs.compile_counter()``). ``engine_decode`` takes the greedy
next token (the argmax of its logits) itself and returns it beside the
logits and the caches, so a step's tokens can feed the next step without
a trip to the host.

A call of :meth:`Engine.step` gives every active row one token, on the
host. Where every cache leaf is written at a row's position (every block
attends; no SSM state is advanced in place), the engine runs one step
ahead of the host: before it waits for step k's tokens it dispatches step
k+1 on them. The host knows without those tokens which rows finish at
step k (a row finishes at its token budget), so step k+1 runs with those
rows on the null page, where it can write into no page the next admission
may hand out. The next call takes step k+1 as its step, unless an
admission changed the batch in between: then that step's tokens are
dropped and step k+1 is dispatched again over the new batch, which writes
each old row's cache at the same position before any layer reads it.
Engines with SSM state step synchronously.
``counters["decode_ahead"]`` counts steps dispatched before the previous
step's tokens reached the host, ``counters["decode_redone"]`` those of
them dropped for an admission.

Admission and the decode step are cut into ``repro.obs`` spans, off
unless ``obs.enable(True)`` was called: ``engine.admit`` holds one
``engine.prefill`` (batch, prefill program, first tokens to the host)
and one ``engine.splice`` (per-row slices and cache splices) per prefill
group; ``engine.step`` holds ``engine.decode`` (the inputs and the
dispatch of the step, where not already in flight, and of the step run
ahead), ``engine.tokens`` (the step's tokens to the host, where the host
waits for the chip) and ``engine.account`` (slot accounting).
``Engine.counters`` also counts prefill rows, padding included, and the
padding rows.

The engine holds its weights in the layout its decode program reads. At
construction it lowers ``engine_decode`` with the parameters' layouts
left to the compiler (:func:`decode_formats`), puts each leaf whose
chosen format differs from the one it has into that format
(:func:`hold_in`: new arrays, the caller's stay as they were), and jits
``engine_decode`` and every ``engine_prefill`` against those formats, so
the step reads each layer's weights where they lie instead of copying
its slice of them into another layout every step.
``counters["weights_relaid_bytes"]`` counts the bytes so placed: on a
TPU the q, k and v projections of a dense transformer, whose head-major
dot output wants them contraction dim minor; 0 where the compiler keeps
every default (the CPU).

MTC workflows (Montage-style DAGs of inference tasks) are driven by
``repro.core.tre.MTCRuntimeEnv``, which feeds this engine only tasks whose
dependencies completed — the DawningCloud "trigger monitor" role. The env
treats each batching slot as one node; ``repro.serve.driver.ServeDriver``
is the trace-rate driver wiring (engine steps advance a ``TickClock``,
finished requests are reported back via ``env.finish``) and
``examples/serve_workflow.py`` the reference entry point.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from repro import obs
from repro.models.lm import LM, Runtime


@dataclass
class Request:
    rid: int
    tokens: np.ndarray            # (P,) or (P,ncb) prompt tokens
    max_new_tokens: int = 16
    patches: np.ndarray | None = None
    out_tokens: list = field(default_factory=list)
    done: bool = False
    rejected: bool = False        # oversize for the cache: never admitted


def decode_program(lm: LM, rt: Runtime, paged: bool):
    """The engine's decode step ``engine_decode(params, tokens, lengths,
    caches[, page_table])``: each row's last token ((B,) or (B, ncb)
    int32) written at its length. Returns the greedy next tokens, in the
    shape of ``tokens``, the logits they were taken from, and the updated
    caches."""
    def greedy(logits, caches):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, caches

    if not paged:
        def engine_decode(p, t, l, c):
            return greedy(*lm.decode(p, rt, t[:, None], l, c))
    else:
        def engine_decode(p, t, l, c, pt):
            return greedy(*lm.decode(p, rt, t[:, None], l, c,
                                     page_table=pt))
    return engine_decode


def decode_formats(engine_decode, params, *args):
    """The formats the compiler chooses for ``params`` when it compiles
    ``engine_decode(params, *args)`` (the caches, ``args[2]``, donated)
    with the parameters' layouts left to it and each leaf kept on its own
    sharding; ``args`` keep the placement they have."""
    auto = jax.tree.map(lambda x: Format(Layout.AUTO, x.sharding), params)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        params)
    compiled = jax.jit(engine_decode,
                       in_shardings=(auto,) + (None,) * len(args),
                       donate_argnums=(3,)).lower(abstract, *args).compile()
    return compiled.input_formats[0][0]


def relaid_bytes(params, formats) -> int:
    """Bytes of the leaves of ``params`` not held in the format that
    ``formats`` names for them."""
    return sum(x.size * x.dtype.itemsize
               for x, f in zip(jax.tree.leaves(params),
                               jax.tree.leaves(formats))
               if x.format != f)


def hold_in(params, formats):
    """``params`` with each leaf that is not in its format in ``formats``
    copied into it; the other leaves, and the caller's arrays, as they
    are. The program that copies is never written to JAX's persistent
    compilation cache: loaded from there, it returns its result in the
    default layout, whatever layout it was compiled for (jax 0.9)."""
    key = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, key)
    jax.config.update(key, math.inf)
    try:
        return jax.tree.map(lambda x, f: x if x.format == f
                            else jax.device_put(x, f), params, formats)
    finally:
        jax.config.update(key, was)


class Engine:
    """``prefill_chunk``: run every grouped prefill at this fixed batch
    size (padding the final partial chunk) so the JIT specializes once per
    *prompt shape* instead of once per (prompt shape, group size) pair —
    a multi-tenant fleet's admit windows produce many distinct group
    sizes, and unchunked each would compile its own prefill. ``None``
    keeps the exact-size behavior (single-tenant streams see few sizes)."""

    def __init__(self, lm: LM, params, rt: Runtime, *, max_batch: int,
                 max_len: int, prefill_chunk: int | None = None,
                 page_size: int | None = None):
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.prefill_chunk = prefill_chunk
        self.lm, self.rt = lm, rt
        self.max_batch, self.max_len = max_batch, max_len
        self.page_size = page_size
        self.lengths = np.zeros((max_batch,), np.int32)
        self.active: dict[int, Request] = {}     # slot -> request
        self.free = list(range(max_batch))
        ncb = lm.cfg.n_codebooks
        tok_shape = (max_batch,) if ncb <= 1 else (max_batch, ncb)
        if page_size is None:
            self.pager = None
            self.caches = lm.init_cache(max_batch, max_len)
            tables = ()
        else:
            # physical paged KV: attention caches live in one shared page
            # pool; a slot's cache is the pages its table row maps. Page 0
            # is the reserved null page — every inactive row's table points
            # at it, so the decode step's unconditional scatter (all rows
            # write every step) can never corrupt a page owned by an
            # active slot.
            if page_size < 1 or max_len % page_size:
                raise ValueError(
                    f"max_len ({max_len}) must be a positive multiple of "
                    f"page_size ({page_size})")
            if rt.decode_kv_shard(lm.cfg) == "seq":
                raise ValueError(
                    "paged KV is incompatible with decode_kv_shard='seq'")
            from repro.serve.paged import PagedKVAllocator
            self.pages_per_slot = max_len // page_size
            n_pages = 1 + max_batch * self.pages_per_slot
            self.pager = PagedKVAllocator(n_pages, page_size=page_size,
                                          reserve_null=True)
            self.caches = lm.init_paged_cache(max_batch, n_pages, page_size)
            self._page_table = np.zeros((max_batch, self.pages_per_slot),
                                        np.int32)
            tables = (self._page_table,)
        engine_decode = decode_program(lm, rt, paged=page_size is not None)
        # the weights, once, in the layout the decode program reads; the
        # prefill programs are compiled against it too
        self.formats = decode_formats(
            engine_decode, params, jax.ShapeDtypeStruct(tok_shape, jnp.int32),
            self.lengths, self.caches, *tables)
        self.params = hold_in(params, self.formats)
        self._decode = jax.jit(
            engine_decode, in_shardings=(self.formats, None, None, None)
            + (None,) * len(tables), donate_argnums=(3,))
        self._prefill = {}
        self.steps = 0
        # a step dispatched ahead of the host can be dropped and run again
        # exactly only where each cache leaf is written at a row's position
        # (attention K/V), none advanced in place (SSM state)
        self._run_ahead = all(lm.cfg.block_kind(i) == "attn"
                              for i in range(lm.cfg.pattern_period))
        self._ahead = None      # the step in flight: its tokens, on device
        # the host's tokens go on the device where the step leaves its
        # own, so that the program sees one kind of token input and
        # compiles once
        self._tok_sharding = jax.tree.leaves(self.params)[0].sharding
        # rows run through prefill, and of them the padding rows that
        # repeat a group's last request to fill ``prefill_chunk``; bytes of
        # weights held in a layout other than the one they came in; steps
        # dispatched before the previous step's tokens reached the host,
        # and of them those dropped because an admission changed the batch
        self.counters = {"prefill_rows": 0, "prefill_rows_padded": 0,
                         "weights_relaid_bytes": relaid_bytes(params,
                                                              self.formats),
                         "decode_ahead": 0, "decode_redone": 0}
        # ---- vectorized slot accounting ----
        self._active_mask = np.zeros((max_batch,), bool)
        self._last_tok = np.zeros(tok_shape, np.int32)
        # generated tokens per slot (admit writes index 0; step appends).
        # max_new_tokens <= max_len is enforced at admit, +1 covers the
        # prefill token of a budget-1 request
        self._out_buf = np.zeros((max_batch, max_len + 1) + tok_shape[1:],
                                 np.int32)
        self._out_len = np.zeros((max_batch,), np.int64)
        self._budget = np.zeros((max_batch,), np.int64)
        self._admit_seq = np.zeros((max_batch,), np.int64)
        self._seq = 0

    @property
    def active_count(self) -> int:
        return len(self.active)

    # ---------------------------------------------------------- prefill
    def _prefill_fn(self, plen: int, has_patches: bool):
        key = (plen, has_patches)
        if key not in self._prefill:
            def engine_prefill(params, batch):
                return self.lm.prefill(params, self.rt, batch)
            self._prefill[key] = jax.jit(engine_prefill,
                                         in_shardings=(self.formats, None))
        return self._prefill[key]

    def _splice_caches(self, slot: int, pre_caches):
        """Write a prefill cache (batch=1, seq=P) into the slot.

        Each update replaces the engine's only reference to the leaf it
        updates, so at most one leaf has a second version alive: the
        updates are not donated, and the chip has no room for a second
        cache beside the weights."""
        paged = self.pager is not None
        row = self._page_table[slot] if paged else None

        def updates(attn, src):
            """(start, piece) pairs that write ``src`` into its leaf."""
            if not (paged and attn):
                # attn kv: src (R,1,P,KVH,hd) -> dst (R,B,S,KVH,hd) at
                # [:,slot,:P]; ssm conv: src (R,1,k-1,ch) -> dst
                # (R,B,k-1,ch); ssm state: src (R,1,nh,hp,ds) -> dst
                # (R,B,nh,hp,ds)
                yield (0, slot) + (0,) * (src.ndim - 2), src
                return
            # paged attention KV: page-sized chunks of src (R,1,P,KVH,hd)
            # into dst (R,n_pages,page_size,KVH,hd) at (0, row[j], 0, 0, 0)
            ps, P = self.page_size, src.shape[2]
            for j0 in range(0, P, ps):
                yield ((0, int(row[j0 // ps]), 0, 0, 0),
                       jax.lax.dynamic_slice_in_dim(src, j0,
                                                    min(ps, P - j0), axis=2))

        for key in list(self.caches):
            attn = self.lm.cfg.block_kind(int(key[3:])) == "attn"
            leaves, tree = jax.tree.flatten(self.caches.pop(key))
            for n, src in enumerate(jax.tree.leaves(pre_caches[key])):
                src = src.astype(leaves[n].dtype)
                for start, piece in updates(attn, src):
                    leaves[n] = jax.lax.dynamic_update_slice(leaves[n], piece,
                                                             start)
            self.caches[key] = jax.tree.unflatten(tree, leaves)

    def admit(self, req: Request) -> bool:
        return bool(self.admit_many([req]))

    def admit_many(self, reqs: list[Request]) -> list[Request]:
        """Admit requests into free slots (as many as fit, in order).

        Admissions are grouped by (prompt length, has-patches) and each
        group runs batched prefill forward passes; per-slot splices then
        scatter the group's caches. Returns the admitted requests — the
        caller keeps the remainder for the next admit window. That
        returned-subset contract is load-bearing: every engine adapter
        (``EmulatedEngine``, ``JaxEngineAdapter``, the fleet's
        ``PartitionedEngine``) returns what it admitted so
        ``ServeDriver._flush_admissions`` can requeue a truncated batch's
        remainder instead of dropping jobs on the floor.

        A request whose prompt + patches + ``max_new_tokens`` exceeds
        ``max_len`` can never be served: it is rejected *individually*
        (``req.rejected = req.done = True``, excluded from the returned
        list, no slot consumed) — never raised. Raising mid-batch used to
        abort the whole admit window, and only requests inside the free
        window were validated at all, so an oversize request parked
        beyond it aborted a *later* window after its slots were popped.

        Without ``prefill_chunk`` each distinct (prompt length, group
        size) pair JIT-specializes the prefill once — keep prompt lengths
        to a small discrete set; with it, groups run in fixed-size
        (padded) chunks, bounding specialization to one per prompt shape.
        """
        with obs.span("engine.admit"):
            groups: dict[tuple[int, bool], list[tuple[int, Request]]] = {}
            admitted: list[Request] = []
            order: dict[int, int] = {}          # slot -> call-order seq
            for req in reqs:
                if not self.free:
                    break
                plen = len(req.tokens)
                n_img = (self.lm.cfg.n_patches if req.patches is not None
                         else 0)
                if plen + n_img + req.max_new_tokens > self.max_len:
                    req.rejected = True
                    req.done = True
                    continue
                slot = self.free.pop()
                if self.pager is not None:
                    need = -(-(plen + n_img + req.max_new_tokens)
                             // self.page_size)
                    pages = self.pager.alloc(slot, need)
                    self._page_table[slot] = 0
                    self._page_table[slot, :len(pages)] = pages
                order[slot] = self._seq
                self._seq += 1
                groups.setdefault((plen, req.patches is not None),
                                  []).append((slot, req))
                admitted.append(req)
            if admitted and self._ahead is not None:
                # the step run ahead left the new rows out: the next step
                # dispatches it again over the new batch
                self._ahead = None
                self.counters["decode_redone"] += 1
            step = self.prefill_chunk
            for (plen, has_patches), members in groups.items():
                for i0 in range(0, len(members), step or len(members)):
                    part = members[i0:i0 + step] if step else members
                    self._prefill_group(plen, has_patches, part, order,
                                        pad_to=step)
            return admitted

    def _prefill_group(self, plen: int, has_patches: bool, members,
                       order: dict[int, int],
                       pad_to: int | None = None) -> None:
        """One prefill forward pass for same-shape requests; splice each
        row's cache into its slot. ``pad_to`` fixes the batch dimension
        (repeating the last row; padded outputs are discarded) so the
        compiled prefill is reused across admit windows of any size."""
        k = len(members)
        n_img = self.lm.cfg.n_patches if has_patches else 0
        with obs.span("engine.prefill"):
            rows = [np.asarray(r.tokens) for _, r in members]
            if pad_to and k < pad_to:
                rows.extend([rows[-1]] * (pad_to - k))
            batch = {"tokens": jnp.asarray(np.stack(rows))}
            if has_patches:
                prows = [np.asarray(r.patches) for _, r in members]
                if pad_to and k < pad_to:
                    prows.extend([prows[-1]] * (pad_to - k))
                batch["patches"] = jnp.asarray(np.stack(prows))
            logits, pre_caches, _ = self._prefill_fn(plen, has_patches)(
                self.params, batch)
            # (k,) or (k, ncb): the first tokens, on the host
            toks = np.asarray(jnp.argmax(logits, axis=-1))[:k]
        self.counters["prefill_rows"] += len(rows)
        self.counters["prefill_rows_padded"] += len(rows) - k
        with obs.span("engine.splice"):
            for i, (slot, _) in enumerate(members):
                self._splice_caches(slot, jax.tree.map(
                    lambda a, _i=i: jax.lax.dynamic_slice_in_dim(
                        a, _i, 1, axis=1),
                    pre_caches))
        slots = np.array([s for s, _ in members])
        for i, (slot, req) in enumerate(members):
            self.active[slot] = req
            req.out_tokens.append(toks[i])
        self.lengths[slots] = plen + n_img
        self._last_tok[slots] = toks
        self._out_buf[slots, 0] = toks
        self._out_len[slots] = 1
        self._budget[slots] = [r.max_new_tokens for _, r in members]
        self._active_mask[slots] = True
        # call-order seqs (NOT group order): same-step finishes must
        # come back in admission order across shape groups, matching
        # EmulatedEngine and the emulator's per-slot event queue
        self._admit_seq[slots] = [order[s] for s, _ in members]

    # ----------------------------------------------------------- decode
    def step(self) -> list[Request]:
        """One decode step for all active slots: one token each, on the
        host. Returns the requests that finished."""
        if not self.active:
            return []
        with obs.span("engine.step"):
            with obs.span("engine.decode"):
                toks = self._ahead
                if toks is None:
                    toks = self._dispatch(jax.device_put(
                        self._last_tok.copy(), self._tok_sharding))
                stepped, done = self._advance()
                self._ahead = None
                if self._run_ahead and self._active_mask.any():
                    self._ahead = self._dispatch(toks)
                    self.counters["decode_ahead"] += 1
            with obs.span("engine.tokens"):
                # (B,) or (B, ncb), on the host: waits for the step
                nxt = np.asarray(toks)
            with obs.span("engine.account"):
                return self._account(nxt, stepped, done)

    def _dispatch(self, toks):
        """The decode program on ``toks``, every slot's last token on the
        device, at the host's lengths and page table. Keeps the updated
        caches; returns the step's tokens, still on the device. The host's
        arrays go in as copies: it changes its own while the step may
        still be queued."""
        tables = () if self.pager is None else (self._page_table.copy(),)
        toks, _, self.caches = self._decode(
            self.params, toks, self.lengths.copy(), self.caches, *tables)
        return toks

    def _advance(self):
        """What the host knows of a dispatched step before its tokens
        arrive: every active slot gets one token and one position, and a
        slot that reaches its budget finishes and goes back to the null
        page. Returns the slots stepped (a mask) and those finished, in
        admission order."""
        stepped = self._active_mask.copy()
        self._out_len[stepped] += 1
        self.lengths += stepped
        done = np.nonzero(stepped & (self._out_len >= self._budget))[0]
        # finish in admission order: the env observes completions in the
        # same order a per-slot event queue would deliver them
        done = done[np.argsort(self._admit_seq[done], kind="stable")]
        self._active_mask[done] = False
        if self.pager is not None:
            for slot in done:
                self.pager.free(int(slot))
            self._page_table[done] = 0
        return stepped, done

    def _account(self, nxt: np.ndarray, stepped: np.ndarray,
                 done: np.ndarray) -> list[Request]:
        """Append the step's tokens ``nxt`` to the ``stepped`` slots, and
        free and return the requests of the ``done`` slots."""
        self._last_tok[stepped] = nxt[stepped]
        self._out_buf[stepped, self._out_len[stepped] - 1] = nxt[stepped]
        self.steps += 1
        finished = []
        for slot in (int(s) for s in done):
            req = self.active.pop(slot)
            req.done = True
            # materialize the slot's output buffer (admit wrote index 0)
            req.out_tokens = [self._out_buf[slot, i]
                              for i in range(int(self._out_len[slot]))]
            self.free.append(slot)
            finished.append(req)
        return finished

    def run(self, requests: list[Request]) -> list[Request]:
        """Serve a list of requests to completion (admitting as slots
        free). Oversize requests come back in the result marked
        ``rejected`` with no output tokens."""
        pending = list(requests)
        done: list[Request] = []
        while pending or self.active:
            if pending and self.free:
                window = pending[:len(self.free)]
                taken = {id(r) for r in self.admit_many(window)}
                for req in window:
                    if req.rejected:
                        done.append(req)
                        taken.add(id(req))
                pending = [r for r in pending if id(r) not in taken]
            done.extend(self.step())
        return done
