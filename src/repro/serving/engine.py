"""EC-DNN_G continuous-batching inference engine.

The paper's Section 4 serving mode — "take the global model as the final
model if there are enough resources at test time" — as one compiled
program per decode step instead of the K-jit-calls-per-token Python loop
it replaces:

  - all K members score the step inside a single jit: params and the
    kv_cache pool carry a leading member axis and a jax.vmap over it
    batches every layer's matmuls across the ensemble;
  - each batch row is an independent *slot* at its own sequence position
    (models/transformer.decode_step_slots), so requests of different
    lengths share the decode batch — the substrate continuous batching
    (scheduler.py) admits into and evicts from;
  - member distributions fuse on-device via core.ensemble
    .ensemble_log_probs (Eqn 6 in log space) under a (K,) quorum vector:
    zeroing a member's weight degrades gracefully to the surviving
    subset, mirroring ring_relabel's straggler policy, with no recompile
    (the quorum is a traced argument);
  - sampling, output bookkeeping and EOS/length eviction flags all
    happen inside the jitted step, so the host loop is dispatch-only;
  - prompts go through a SECOND compiled kernel: prefill (also vmapped
    over members; slot index traced) consumes a whole prompt chunk of
    one slot per program and materializes every prompt position's
    KV/recurrent state straight into that slot's cache row (slot_row ->
    chunk forward -> write_slot_row, the prefill-then-insert idiom), so
    a request is decode-ready after ceil(prompt_len / prefill_chunk)
    programs instead of prompt_len steps, costs O(chunk) — not
    O(n_slots x chunk) — and its first generated token is sampled from
    the prefill program's last-token logits.  prefill_chunk=0 keeps the
    original one-token-per-step teacher-forcing path as a reference
    baseline.

Multi-device (mesh=...): the member axis is the unit of parallelism.
The paper's global model is K INDEPENDENT members (Eqn 6), so at
serving time nothing crosses members until the final fusion — sharding
the leading (K,) axis of the stacked params, the cache pool, and the
quorum vector over the "member" axis of a ("member", "data") mesh
(common.sharding.local_mesh) makes per-device cache bytes and FLOPs
scale with K/M instead of K.  Every kernel above then runs under
shard_map: each device vmaps only its local members and the Eqn-6
fusion becomes a psum-style cross-member reduction
(core.ensemble.ensemble_log_probs_psum) — one pmax + one psum of fused
(B, V) partials is ALL the inter-device traffic per step; K full
distributions never move.  Slot state and sampling are replicated, the
quorum stays a traced argument (straggler drop still recompiles and
reshards nothing, mirroring ring_relabel's local-worker placement
story), and mesh=None keeps the original single-jit path bit-identical
as the reference baseline.  A 1-device local_mesh runs the same
shard_map program (collectives become identity), so CPU CI exercises
the mesh code path without multiple devices.

Paged cache (paged=True): the contiguous pool reserves a max_seq KV row
per member per layer per slot — the ensemble's K-fold model-cost tax
(paper §1) paid again in cache bytes, however short the requests.  The
paged pool spends bytes on TOKENS IN FLIGHT instead: full-attention
planes become fixed-size pages shared by all slots behind a per-slot
page table (kv_cache.PageAllocator, pure host policy; the table is a
traced input, so allocation never recompiles), admission bounds by free
pages rather than free slots, decode grows one page per boundary
crossing with zero device sync (a host-side position mirror), and the
Pallas kernel kernels/paged_attention.py reads only a slot's live pages
— O(len) per step, not O(max_seq).  paged=False keeps the contiguous
pool bit-identical as the reference baseline; docs/serving.md "Paged
cache" has the layout diagram and lifecycle.

Every decode in the repo (launch/serve.py CLI, examples, benchmarks,
the scheduler) goes through EnsembleEngine.prefill/step — one path.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common import sharding as shd
from repro.common.types import ModelConfig
from repro.core import ensemble as ens
from repro.models import attention as attn_mod
from repro.models import transformer as tf
from repro.serving import kv_cache, sampling
from repro.serving import prefix as prefix_mod


class SlotState(NamedTuple):
    """Device-resident per-slot serving state (one row per batch slot)."""

    tok: jax.Array         # (B,)   next input token
    pos: jax.Array         # (B,)   tokens consumed so far (== cache idx)
    prompt: jax.Array      # (B,P)  padded prompt buffer
    prompt_len: jax.Array  # (B,)
    max_new: jax.Array     # (B,)   per-request generation budget
    n_gen: jax.Array       # (B,)   tokens emitted so far
    active: jax.Array      # (B,)   slot occupied by a request
    done: jax.Array        # (B,)   finished, awaiting host harvest
    out: jax.Array         # (B,G)  emitted tokens
    key: jax.Array         # PRNG carried across steps
    temp: jax.Array        # (B,)   per-request sampling temperature
    topk: jax.Array        # (B,)   per-request top-k (0 = full vocab)
    skey: jax.Array        # (B,2)  per-request base PRNG key
    draft: jax.Array       # (B,)   speculative drafting enabled


def _param_spec(params):
    """(treedef, [(shape, dtype)]) of a RAW (pre-absorption) stack —
    what swap_params validates incoming checkpoints against."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return treedef, [(x.shape, x.dtype) for x in leaves]


class EnsembleEngine:
    """Vmapped-member decode engine over a fixed pool of batch slots.

    stacked_params: member params with a leading (K,) axis (the layout
    `jax.vmap(lambda k: tf.init(k, cfg))(keys)` produces and training
    checkpoints store).  K = 1 serves a single/compressed model
    (EC-DNN_L) through the identical path.

    mesh: None (default) runs the single-device reference path — one
    jit, vmap over all K members.  A ("member", "data") mesh from
    `common.sharding.local_mesh` shards the leading (K,) member axis of
    params / cache pool / quorum over "member" (K must divide evenly)
    and compiles every kernel under shard_map: each device holds and
    scores K/M members and only fused log-prob partials cross devices
    (`core.ensemble.ensemble_log_probs_psum`).  Slot state replicates,
    so the host API is placement-oblivious — same calls, same shapes,
    same results (token-exact vs mesh=None at float32).
    """

    def __init__(self, cfg: ModelConfig, stacked_params, *,
                 n_slots: int = 8, max_prompt: int = 64, max_out: int = 64,
                 prefill_chunk: Optional[int] = None,
                 temperature: float = 0.0,
                 top_k: int = 0, eos_id: int = -1,
                 quorum: Optional[Sequence[float]] = None, seed: int = 0,
                 mesh=None, paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None,
                 prefix_cache: bool = False, kv_dtype: str = "f32"):
        self.cfg = cfg
        self.n_members = jax.tree.leaves(stacked_params)[0].shape[0]
        self.mesh = mesh
        self.member_shards = (1 if mesh is None
                              else mesh.shape[shd.MEMBER_AXIS])
        if self.n_members % self.member_shards:
            raise ValueError(
                f"mesh member axis {self.member_shards} does not divide "
                f"K={self.n_members} members")
        if kv_dtype not in attn_mod.KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {attn_mod.KV_DTYPES}, "
                f"got {kv_dtype!r}")
        if kv_dtype != "f32" and not paged:
            raise ValueError(
                "kv_dtype != f32 requires paged=True (only paged planes "
                "are stored quantized; the contiguous pool is the "
                "bit-exact reference)")
        self.kv_dtype = kv_dtype
        # swap_params validates incoming RAW trees against the raw spec
        # captured here, BEFORE any absorbed-MLA leaves are added
        self._param_spec = _param_spec(stacked_params)
        if paged:
            stacked_params = tf.absorb_mla_params(cfg, stacked_params)
        if mesh is None:
            self.params = stacked_params
        else:
            self.params = jax.device_put(
                stacked_params,
                shd.make_shardings(mesh, shd.member_pspecs(stacked_params)))
        self.n_slots = n_slots
        self.max_prompt = max_prompt
        self.max_out = max_out
        self.max_seq = max_prompt + max_out
        # prompt tokens consumed per prefill program; 0 disables batched
        # prefill and keeps the per-token teacher-forcing reference
        # path.  None picks the chunk from the engine's own budgets
        # instead of a hardcoded constant: a quarter of max_prompt
        # (floor 32, so short-prompt engines keep the proven default),
        # rounded up to a whole page on paged engines so chunk
        # boundaries and page boundaries line up.  An explicit int
        # always overrides.
        if prefill_chunk is None:
            prefill_chunk = max(32, -(-max_prompt // 4))
            if paged and page_size > 0:
                prefill_chunk = -(-prefill_chunk // int(page_size)) \
                    * int(page_size)
        self.prefill_chunk = min(max(prefill_chunk, 0), max_prompt)
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        self.quorum = (jnp.ones((self.n_members,), jnp.float32)
                       if quorum is None
                       else jnp.asarray(quorum, jnp.float32))
        # paged KV pool: full-attention planes become shared fixed-size
        # pages behind a per-slot page table (kv_cache.PageAllocator);
        # paged=False keeps the contiguous pool BIT-IDENTICAL (none of
        # the code below this constructor changes shape or math).
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.prefix: Optional[prefix_mod.PrefixCache] = None
        if self.paged:
            if cfg.enc_dec:
                raise ValueError(
                    "paged serving does not support enc-dec archs yet "
                    "(stub encoder context is slot-contiguous)")
            if self.page_size <= 0:
                raise ValueError(f"page_size must be > 0, got {page_size}")
            self.pages_per_slot = -(-self.max_seq // self.page_size)
            # default: full capacity (every slot can reach max_seq) —
            # equal logical capacity to the contiguous pool; pass a
            # smaller n_pages to oversubscribe slots against memory
            # (admission then bounds by free pages, Scheduler preempts)
            self.n_pages = (n_slots * self.pages_per_slot
                            if n_pages is None else int(n_pages))
            self.allocator = kv_cache.PageAllocator(
                self.n_pages, self.page_size, n_slots, self.pages_per_slot)
            # host mirror of each slot's request shape: lets the engine
            # grow pages BEFORE dispatching a step, with zero device sync
            # (EOS-early finishes overshoot by <= one page until harvest)
            self._host_pos = np.zeros(n_slots, np.int64)
            self._host_plen = np.zeros(n_slots, np.int64)
            self._host_new = np.zeros(n_slots, np.int64)
            self._host_active = np.zeros(n_slots, bool)
            self._table_stale = True
            if prefix_cache:
                bad = self._prefix_ineligible()
                if bad:
                    raise ValueError(
                        f"prefix_cache needs every layer's positional "
                        f"state in shared pages, but {bad} keeps per-slot"
                        f" state a hit could not skip rebuilding")
                if self.prefill_chunk <= 0:
                    raise ValueError(
                        "prefix_cache needs chunked prefill "
                        "(prefill_chunk > 0): admission starts the "
                        "chunk walk at the hit boundary")
                # each slot's prompt, mirrored host-side: the trie is
                # keyed on token ids and harvests a chain's prefix at
                # release, long after the admit call's arrays are gone
                self._host_prompt = np.zeros((n_slots, max_prompt),
                                             np.int32)
                self.prefix = prefix_mod.PrefixCache(self.page_size)
                self.allocator.cache = self.prefix
        elif prefix_cache:
            raise ValueError("prefix_cache requires paged=True (the "
                             "contiguous pool has no shareable pages)")
        self.cache = kv_cache.init_pool(
            cfg, self.n_members, n_slots, self.max_seq, mesh=mesh,
            page_size=self.page_size if self.paged else 0,
            n_pages=self.n_pages if self.paged else 0,
            kv_dtype=kv_dtype)
        if cfg.enc_dec:
            self.cache["enc"] = self._encode_stub(n_slots)
        self.state = self._blank_state(seed)
        # per-request sampling: requests that do not pin a seed draw
        # their base key from fold_in(engine key, admission counter) —
        # deterministic for a given admission order, distinct per request
        self._req_base_key = jax.random.PRNGKey(seed)
        self._admitted = 0
        self.steps_run = 0
        self.prefills_run = 0
        self.swaps_done = 0
        if mesh is not None:
            self.quorum = jax.device_put(
                self.quorum, NamedSharding(mesh, P(shd.MEMBER_AXIS)))
        # cache + state are donated: the pool is updated in place across
        # the server's lifetime, never reallocated.  Under a mesh every
        # kernel wraps in shard_map first (member axis manual, slot
        # state replicated); in/out shardings match, so donation still
        # reuses the pool's buffers shard by shard.
        pspec, cspec = (shd.member_pspecs(self.params),
                        shd.member_pspecs(self.cache))
        sspec = shd.replicated_pspecs(self.state)
        q, s = P(shd.MEMBER_AXIS), P()
        self._step = self._compile(
            self._step_impl, donate=(1, 2),
            in_specs=(pspec, cspec, sspec, q),
            out_specs=(sspec, cspec))
        self._prefill = self._compile(
            self._prefill_impl, donate=(1, 2),
            in_specs=(pspec, cspec, sspec, q, s),
            out_specs=(sspec, cspec))
        self._update = self._compile(
            self._update_impl, donate=(0, 1),
            in_specs=(cspec, sspec, s, s, s, s, s, s, s, s, s, s),
            out_specs=(sspec, cspec))
        if self.paged:
            # whole-page device copy for copy-on-write admissions:
            # fixed (B,)-shaped src/dst id vectors (sentinel rows
            # no-op), so any COW pattern reuses one compiled program
            self._copy = self._compile(
                lambda cache, src, dst: kv_cache.copy_pages(
                    cache, src, dst, self.n_pages),
                donate=(0,), in_specs=(cspec, s, s), out_specs=cspec)
        self._score = self._compile(
            self._score_impl, donate=(1,),
            in_specs=(pspec, cspec, s, s, q),
            out_specs=(q, s, cspec))

    def _compile(self, fn, donate, in_specs, out_specs):
        """jit a kernel; under a mesh, wrap it in shard_map first.

        Specs are rank-correct pytrees per argument: the member axis of
        params/cache/quorum is manual-sharded, slot state and scalars
        replicate (shorter specs pad with None, so P() on a vector arg
        means fully replicated).  check_vma stays off: outputs declared
        replicated ARE replicated by construction — every cross-member
        quantity goes through a psum/pmax before it reaches them.
        """
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=donate)
        return jax.jit(
            shd.shard_map(fn, self.mesh, in_specs=in_specs,
                          out_specs=out_specs),
            donate_argnums=donate)

    # -- construction -------------------------------------------------------

    def _blank_state(self, seed: int) -> SlotState:
        B, P, G = self.n_slots, self.max_prompt, self.max_out
        zi = lambda *s: jnp.zeros(s, jnp.int32)
        zb = lambda *s: jnp.zeros(s, bool)
        return SlotState(tok=zi(B), pos=zi(B), prompt=zi(B, P),
                         prompt_len=zi(B), max_new=zi(B), n_gen=zi(B),
                         active=zb(B), done=zb(B), out=zi(B, G),
                         key=jax.random.PRNGKey(seed),
                         temp=jnp.zeros((B,), jnp.float32), topk=zi(B),
                         skey=jnp.zeros((B, 2), jnp.uint32), draft=zb(B))

    def _encode_stub(self, batch: int) -> jax.Array:
        """Per-member encoder outputs over stub frame embeddings.

        Audio/VLM frontends are stubs repo-wide (DESIGN §4); per-request
        encoder state is a serving follow-up (ROADMAP).  Computed once —
        the decode loop only reads it.  Under a mesh the (K, B, S, d)
        result is pinned member-sharded like the rest of the pool.
        """
        from repro.models.layers import dtype_of
        enc_in = jnp.zeros((batch, self.cfg.enc_max_frames,
                            self.cfg.d_model), dtype_of(self.cfg))
        enc = jax.jit(jax.vmap(
            lambda p: tf.encode(p, self.cfg, enc_in)))(self.params)
        if self.mesh is not None:
            enc = jax.device_put(
                enc, NamedSharding(self.mesh, shd.member_pspec(enc.ndim)))
        return enc

    # -- jitted kernels -----------------------------------------------------
    # Each kernel body is placement-oblivious: it sees the full (K,) axis
    # on the reference path and the local (K/M,) shard inside shard_map;
    # the only cross-member op is _fuse, which switches to the psum-style
    # reduction on the mesh path.

    def _member_logits(self, params, cache, tok) -> Tuple[jax.Array, dict]:
        """All (local) members score the step in one program.
        -> ((K, B, V), cache).  Paged engines route through
        decode_step_paged (same contract; KV reads go through each
        member's replica of the page table)."""
        step = (tf.decode_step_paged if self.paged
                else tf.decode_step_slots)

        def one(p, c):
            return step(p, self.cfg, c, tok[:, None])

        logits, cache = jax.vmap(one)(params, cache)  # (K, B, 1, V)
        return logits[:, :, 0], cache

    def _fuse(self, member_logits, quorum) -> jax.Array:
        """Eqn-6 log-space fusion under the traced quorum vector.

        Reference path: logsumexp over the full member axis.  Mesh path:
        each shard fuses its local members, then one pmax + one psum
        over "member" combine the shards — only fused (..., V) partials
        cross devices, never K distributions.
        """
        if self.mesh is None:
            return ens.ensemble_log_probs(member_logits, weights=quorum)
        return ens.ensemble_log_probs_psum(member_logits, quorum,
                                           axis_name=shd.MEMBER_AXIS)

    def _step_impl(self, params, cache, st: SlotState, quorum):
        B = st.tok.shape[0]
        # only live slots advance: an inactive / finished slot must not
        # walk pos (and the cache idx) past max_seq while the server
        # idles.  With batched prefill on, mid-prompt slots also hold
        # still here — the prefill program owns the prompt path.
        adv = st.active & ~st.done
        if self.prefill_chunk > 0:
            adv &= st.pos >= st.prompt_len
        old_cache = cache
        logits, cache = self._member_logits(params, cache, st.tok)
        cache = kv_cache.keep_frozen(cache, old_cache, adv)
        logp = self._fuse(logits, quorum)  # (B, V)
        # per-request sampling params; the key for emission i is
        # fold_in(request base key, i), so a preempted-and-resumed
        # request regenerates token-identically
        keys = jax.vmap(jax.random.fold_in)(st.skey, st.n_gen)
        sampled = sampling.sample_slots(keys, logp, st.temp, st.topk)

        pos1 = st.pos + adv.astype(jnp.int32)
        in_prompt = pos1 < st.prompt_len  # next input is teacher-forced
        P = st.prompt.shape[1]
        nxt_prompt = jnp.take_along_axis(
            st.prompt, jnp.minimum(pos1, P - 1)[:, None], axis=1)[:, 0]

        emit = adv & ~in_prompt
        row = jnp.arange(B)
        col = jnp.minimum(st.n_gen, st.out.shape[1] - 1)
        out = st.out.at[row, col].set(
            jnp.where(emit, sampled, st.out[row, col]))
        n_gen = st.n_gen + emit.astype(jnp.int32)
        finished = emit & (n_gen >= st.max_new)
        if self.eos_id >= 0:
            finished |= emit & (sampled == self.eos_id)
        done = st.done | finished
        tok = jnp.where(adv, jnp.where(in_prompt, nxt_prompt, sampled),
                        st.tok)
        return st._replace(tok=tok, pos=pos1, n_gen=n_gen, done=done,
                           out=out), cache

    def _update_impl(self, cache, st: SlotState, release, admit,
                     prompt, plen, max_new, temp, topk, skey, draft,
                     pos0):
        """Evict `release` slots, (re)fill `admit` slots with new requests.

        pos0 (B,): per-admit start position — 0 on a cold admission,
        the prefix-cache hit length when admission attached shared
        pages holding the prompt's first pos0 positions (update_slots
        computes it; always 0 with the prefix cache off, keeping this
        path bit-identical to the pre-prefix engine).  The slot's
        first prefill chunk then starts at pos0, and its first input
        token is prompt[pos0] rather than prompt[0].
        """
        cache = kv_cache.reset_slots(cache, admit, pos0)
        a2 = admit[:, None]
        tok0 = jnp.take_along_axis(prompt, pos0[:, None], axis=1)[:, 0]
        return SlotState(
            tok=jnp.where(admit, tok0, st.tok),
            pos=jnp.where(admit, pos0, st.pos),
            prompt=jnp.where(a2, prompt, st.prompt),
            prompt_len=jnp.where(admit, plen, st.prompt_len),
            max_new=jnp.where(admit, max_new, st.max_new),
            n_gen=jnp.where(admit, 0, st.n_gen),
            active=(st.active & ~release) | admit,
            done=st.done & ~release & ~admit,
            out=jnp.where(a2, 0, st.out),
            key=st.key,
            temp=jnp.where(admit, temp, st.temp),
            topk=jnp.where(admit, topk, st.topk),
            skey=jnp.where(a2, skey, st.skey),
            draft=jnp.where(admit, draft, st.draft)), cache

    def _prefill_impl(self, params, cache, st: SlotState, quorum, slot):
        """Consume up to prefill_chunk prompt tokens of ONE slot in one
        compiled program (members vmapped, like _step_impl).

        The slot index is a traced scalar, so every slot reuses this one
        program; only the selected slot's cache row rides through the
        chunk forward (slot_row -> prefill -> write_slot_row, maxtext's
        prefill-then-insert), so a prefill costs O(chunk) compute — not
        O(n_slots x chunk) — and in-flight neighbors are untouched.  A
        slot whose prompt completes inside this chunk gets its first
        generated token sampled from the chunk's last-token logits: the
        first token comes out of prefill itself, no decode step needed.
        Idle / decode-phase slots are bit-exact no-ops (n_tok == 0).
        """
        C = self.prefill_chunk
        pos, plen = st.pos[slot], st.prompt_len[slot]
        need = st.active[slot] & ~st.done[slot] & (pos < plen)
        n_tok = jnp.where(need, jnp.minimum(C, plen - pos), 0)
        P = st.prompt.shape[1]
        cols = jnp.clip(pos + jnp.arange(C), 0, P - 1)
        chunk = st.prompt[slot][cols][None]  # (1, C)
        row = kv_cache.slot_row(cache, slot)

        if self.paged:
            def one(p, c):
                return tf.prefill_step_paged(p, self.cfg, c, chunk, n_tok)
        else:
            def one(p, c):
                return tf.prefill_slots(p, self.cfg, c, chunk, n_tok[None])

        logits, row = jax.vmap(one)(params, row)  # (K, 1, V)
        cache = kv_cache.write_slot_row(cache, row, slot)
        logp = self._fuse(logits[:, 0], quorum)  # (V,)
        kb = jax.random.fold_in(st.skey[slot], st.n_gen[slot])
        sampled = sampling.sample_slots(
            kb[None], logp[None], st.temp[slot][None],
            st.topk[slot][None])[0]

        pos1 = pos + n_tok
        completed = need & (pos1 >= plen)
        col = jnp.minimum(st.n_gen[slot], st.out.shape[1] - 1)
        out = st.out.at[slot, col].set(
            jnp.where(completed, sampled, st.out[slot, col]))
        n_gen = st.n_gen.at[slot].add(completed.astype(jnp.int32))
        finished = completed & (st.n_gen[slot] + 1 >= st.max_new[slot])
        if self.eos_id >= 0:
            finished |= completed & (sampled == self.eos_id)
        return st._replace(
            tok=st.tok.at[slot].set(jnp.where(completed, sampled,
                                              st.tok[slot])),
            pos=st.pos.at[slot].set(pos1), n_gen=n_gen,
            done=st.done.at[slot].set(st.done[slot] | finished),
            out=out), cache

    def _score_impl(self, params, cache, tok_t, gold_t, quorum):
        """Teacher-forced scoring step: per-member + ensemble NLL.

        m_nll is laid out along the member axis ((K/M,) per shard on the
        mesh path, concatenating back to the global (K,)); e_nll comes
        out of the fused distribution, so it is replicated.
        """
        logits, cache = self._member_logits(params, cache, tok_t)  # (K,B,V)
        lp = ens.member_log_probs(logits)
        gold = jnp.broadcast_to(gold_t[None], logits.shape[:-1])
        m_nll = -jnp.take_along_axis(lp, gold[..., None],
                                     axis=-1)[..., 0].mean(-1)  # (K,)
        e_lp = self._fuse(logits, quorum)
        e_nll = -jnp.take_along_axis(e_lp, gold_t[:, None],
                                     axis=1)[:, 0].mean()
        return m_nll, e_nll, cache

    # -- host API -----------------------------------------------------------

    def validate_request(self, tokens, max_new: int,
                         temperature: Optional[float] = None,
                         top_k: Optional[int] = None,
                         seed: Optional[int] = None) -> np.ndarray:
        """Check a request against the engine's budgets; -> 1-D int32
        prompt.  The single source of truth for admission limits, used
        by update_slots and by Scheduler.submit (reject at the door).
        Per-request sampling params are optional (None = engine
        default); out-of-range values raise against the NAMED limits in
        serving/sampling.py (temperature/seed) and the model's
        vocab_size (top_k)."""
        t = np.asarray(tokens, np.int32).reshape(-1)
        if not 0 < t.size <= self.max_prompt:
            raise ValueError(f"prompt len {t.size} not in "
                             f"[1, {self.max_prompt}]")
        if not 0 < max_new <= self.max_out:
            raise ValueError(f"max_new {max_new} not in "
                             f"[1, {self.max_out}]")
        if temperature is not None and not (
                sampling.MIN_TEMPERATURE <= float(temperature)
                <= sampling.MAX_TEMPERATURE):
            raise ValueError(
                f"temperature {temperature} not in [MIN_TEMPERATURE="
                f"{sampling.MIN_TEMPERATURE}, MAX_TEMPERATURE="
                f"{sampling.MAX_TEMPERATURE}]")
        if top_k is not None and not (
                0 <= int(top_k) <= self.cfg.vocab_size):
            raise ValueError(
                f"top_k {top_k} not in [0, vocab_size="
                f"{self.cfg.vocab_size}]")
        if seed is not None and not (
                sampling.MIN_SEED <= int(seed) <= sampling.MAX_SEED):
            raise ValueError(
                f"seed {seed} not in [MIN_SEED={sampling.MIN_SEED}, "
                f"MAX_SEED={sampling.MAX_SEED}]")
        if self.paged:
            need = self.allocator.pages_for(t.size + max_new)
            if need > self.n_pages:
                # could never complete even with the whole pool to
                # itself: preemption would loop forever — reject here
                raise ValueError(
                    f"request needs {need} pages ({t.size}+{max_new} "
                    f"tokens at page_size={self.page_size}) but the pool "
                    f"holds {self.n_pages}")
        return t

    # -- paged-pool host accounting -----------------------------------------

    def _prefix_ineligible(self) -> Optional[str]:
        """Why this config cannot reuse cached prefix pages (None = it
        can).  A prefix hit skips prefill for positions [0, hit), so
        EVERY layer's positional state for those positions must live in
        the shared pages: layers that keep per-slot planes
        (sliding-window attention below max_seq, linear-attention
        recurrent states) or per-slot ffn carries (rwkv_cmix's
        cmix_shift) would come up blank for the skipped positions."""
        for _, specs in self.cfg.segments():
            for spec in specs:
                if not tf.layer_pages(self.cfg, spec, self.max_seq):
                    return (f"mixer {spec.mixer!r} keeps per-slot "
                            f"(non-paged) cache planes")
                if spec.ffn == "rwkv_cmix":
                    return "ffn 'rwkv_cmix' carries per-slot cmix_shift"
        return None

    def _sync_table(self):
        """Push the allocator's page table to the device pool (every
        member carries a replica, so the kernels stay member-vmapped)."""
        tbl = jnp.asarray(self.allocator.table())
        arr = jnp.broadcast_to(tbl[None], (self.n_members,) + tbl.shape)
        if self.mesh is not None:
            arr = jax.device_put(
                arr, NamedSharding(self.mesh, shd.member_pspec(arr.ndim)))
        self.cache["page_table"] = arr
        self._table_stale = False

    def _host_decoding(self) -> np.ndarray:
        """(B,) host's view of slots whose NEXT step writes cache at
        _host_pos — the mirror of _step_impl's `adv` (EOS-early
        finishes are invisible here; they over-hold <= one page until
        harvest releases the slot)."""
        live = self._host_active & (
            self._host_pos < self._host_plen + self._host_new)
        if self.prefill_chunk > 0:
            live &= self._host_pos >= self._host_plen  # prefill owns prompt
        return live

    def reserve_decode_pages(self) -> list:
        """Grow each decoding slot's page chain to cover this step's
        write position; -> slots the dry free list left STARVED (the
        caller — Scheduler — must preempt or release before step()).
        No-op list on contiguous engines."""
        if not self.paged:
            return []
        starved = []
        for b in np.nonzero(self._host_decoding())[0]:
            pos = int(self._host_pos[b])
            if self.allocator.holds(b, pos):
                continue
            if self.allocator.alloc(b, pos // self.page_size + 1):
                self._table_stale = True
            else:
                starved.append(int(b))
        if self._table_stale:
            self._sync_table()
        return starved

    def _release_slot(self, b: int):
        """Recycle slot b's chain and host mirrors.  With the prefix
        cache on, the chain's VALID prompt prefix is offered to the trie
        first (release is the only time a chain's content is final):
        claimed pages survive as cached prefix pages — evictable once
        unreferenced — while everything else (decode tail, deduped
        prompt pages) returns to the free list via refcount decrements.
        Only min(pos, plen) tokens are inserted: a preempted mid-prompt
        slot has only written that far, and decode tokens past the
        prompt are per-request content no other request should match.
        """
        if self.prefix is not None and self._host_plen[b] > 0:
            valid = int(min(self._host_pos[b], self._host_plen[b]))
            n = self.allocator.pages_for(valid)
            chain = self.allocator.chain(b)
            if valid > 0 and len(chain) >= n:
                self.prefix.insert(self._host_prompt[b, :valid],
                                   chain[:n])
            self._host_prompt[b, :] = 0
        self.allocator.release(b)
        self._host_active[b] = False
        self._host_pos[b] = 0
        self._host_plen[b] = self._host_new[b] = 0

    def admit_cost(self, tokens) -> int:
        """Pages admitting this prompt would consume RIGHT NOW:
        worst-case ceil(plen/page) minus matched full pages some live
        slot already references (attaching those is a pure refcount
        bump).  Ref-0 trie pages are NOT discounted — they are already
        counted once in available_pages, and a partial tail's page is
        never discounted (its COW copy consumes a fresh page).  Uses
        the trie's read-only peek, so costing a queue of candidates
        skews neither hit-rate telemetry nor LRU order.  The
        Scheduler's admission gate pairs this with admission_headroom.
        """
        t = np.asarray(tokens, np.int32).reshape(-1)
        cost = self.allocator.pages_for(t.size)
        if self.prefix is None or t.size <= 1:
            return cost
        _, full, _ = self.prefix.peek(t.tolist(), t.size - 1)
        return cost - sum(1 for p in full if self.allocator.ref(p) > 0)

    def admission_headroom(self, releasing: Sequence[int] = ()) -> int:
        """Pages an admission batch can draw on: the allocator's
        available pool (free list + evictable trie pages) plus what
        releasing the given slots would certainly return (their chain
        pages at refcount 1 that the trie does not keep).  Conservative:
        a releasing slot's trie-claimed pages become evictable — also
        headroom — but are only counted once they get there."""
        if not self.paged:
            return -1
        return self.allocator.available_pages + sum(
            self.allocator.reclaimable_pages(int(b)) for b in releasing)

    @property
    def free_pages(self) -> int:
        return self.allocator.free_pages if self.paged else -1

    def assert_pool_whole(self) -> None:
        """Drained-state check: no slot holds pages, the pool's global
        accounting is consistent (kv_cache.PageAllocator
        .check_invariants), and every page is free or trie-evictable.
        Raises AssertionError naming the leak.  The fleet soak, the
        cancellation tests, and a replica's post-drain hygiene all gate
        on this — a page that survives a full drain is a leak the
        admission headroom would silently repay forever.  No-op on
        contiguous engines (nothing to leak)."""
        if not self.paged:
            return
        a = self.allocator
        held = {b: a.held_pages(b) for b in range(self.n_slots)
                if a.held_pages(b)}
        assert not held, f"drained engine still holds pages: {held}"
        a.check_invariants()
        assert a.available_pages == a.n_pages, \
            (f"{a.n_pages - a.available_pages} pages neither free nor "
             f"evictable after drain ({a.free_pages} free, "
             f"{a.available_pages} available of {a.n_pages})")

    def page_stats(self) -> dict:
        """Free-list occupancy telemetry (placement summaries, client
        reports).  Empty on contiguous engines."""
        if not self.paged:
            return {}
        a = self.allocator
        pb = kv_cache.page_bytes(self.cache, a.n_pages)
        stats = {"n_pages": a.n_pages, "page_size": a.page_size,
                 "free_pages": a.free_pages, "used_pages": a.used_pages,
                 "available_pages": a.available_pages,
                 "shared_pages": a.shared_pages,
                 "pages_per_slot": a.pages_per_slot,
                 "low_water_pages": a.low_water,
                 "kv_dtype": self.kv_dtype,
                 "kv_quantized": int(self.kv_dtype in ("int8", "fp8")),
                 "page_bytes": pb,
                 "bytes_per_token": pb // max(a.page_size, 1)}
        if self.prefix is not None:
            stats.update(self.prefix.stats())
            stats["cow_pages"] = a.cow_count
            stats["shared_attaches"] = a.shared_attach_count
        return stats

    def step(self) -> SlotState:
        """Advance every slot one token (one compiled program).

        All K members score the step — vmapped in one jit on the
        reference path, K/M members per device under shard_map on the
        mesh path (fused log-probs are the only cross-device traffic).
        Returns the replicated SlotState; the cache pool (leading (K,)
        member axis, sharded over "member" when a mesh is set) advances
        in place via donation.

        Paged engines grow each decoding slot's page chain first
        (reserve_decode_pages); a dry free list raises — callers that
        can preempt (Scheduler) reserve themselves before stepping.
        """
        if self.paged:
            starved = self.reserve_decode_pages()
            if starved:
                raise RuntimeError(
                    f"paged pool out of pages for decoding slots "
                    f"{starved} ({self.allocator.free_pages} free of "
                    f"{self.n_pages}); release finished slots or preempt "
                    f"(Scheduler.run does) before stepping")
        self.state, self.cache = self._step(self.params, self.cache,
                                            self.state, self.quorum)
        self.steps_run += 1
        if self.paged:
            adv = self._host_decoding()
            self._host_pos[adv] += 1
        return self.state

    def prefill(self, slot: int) -> SlotState:
        """Advance one mid-prompt slot by up to prefill_chunk prompt
        tokens (one compiled program, slot index traced — every slot
        reuses it); a slot whose prompt completes emits its first
        generated token from this same program.

        An admitted request is decode-ready after
        ceil(prompt_len / prefill_chunk) prefill programs instead of
        prompt_len engine steps, and the program touches only this
        slot's cache row — in-flight neighbors don't pay for it.
        """
        if self.prefill_chunk <= 0:
            raise ValueError("engine built with prefill_chunk=0 "
                             "(per-token reference path)")
        if not 0 <= int(slot) < self.n_slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.n_slots})")
        if self.paged and self._table_stale:
            self._sync_table()
        self.state, self.cache = self._prefill(
            self.params, self.cache, self.state, self.quorum,
            jnp.asarray(slot, jnp.int32))
        self.prefills_run += 1
        if self.paged:
            b = int(slot)
            left = self._host_plen[b] - self._host_pos[b]
            if self._host_active[b] and left > 0:
                self._host_pos[b] += min(self.prefill_chunk, int(left))
        return self.state

    def update_slots(self, release: Sequence[int] = (),
                     admits: Sequence[tuple] = ()):
        """Evict finished slots and admit new requests.

        admits: (slot, prompt_tokens, max_new) triples, or 4-tuples
        whose last element is an options dict with any of
        {"temperature", "top_k", "seed", "draft"} — per-request
        sampling/speculation overrides (None/missing = engine default;
        a request with no seed gets fold_in(engine key, admission
        counter), so admission order fixes its draws).  Fixed-shape
        masked updates, so any admission pattern reuses one compiled
        program.  Admission is a slot-axis operation: it touches every
        member's row of the (K, ...) pool identically, so the mesh path
        runs it shard-local with zero communication.

        Returns {slot: hit_tokens} for admissions the prefix cache
        served (serving/prefix.py): those slots start prefilling at
        position `hit`, so callers that drive prefill themselves
        (generate, Scheduler) owe ceil((plen - hit) / prefill_chunk)
        chunks, not ceil(plen / chunk).  Empty whenever the prefix
        cache is off — and the whole path below is then bit-identical
        to the pre-prefix engine (pos0 stays all-zero).
        """
        B, P = self.n_slots, self.max_prompt

        def check_slot(b) -> int:
            # validate BEFORE indexing: numpy wraparound would silently
            # alias slot -1 onto the last slot
            b = int(b)
            if not 0 <= b < B:
                raise ValueError(f"slot {b} out of range [0, {B})")
            return b

        rel = np.zeros((B,), bool)
        adm = np.zeros((B,), bool)
        prompt = np.zeros((B, P), np.int32)
        plen = np.zeros((B,), np.int32)
        mnew = np.zeros((B,), np.int32)
        temp = np.full((B,), self.temperature, np.float32)
        topk = np.full((B,), self.top_k, np.int32)
        skey = np.zeros((B, 2), np.uint32)
        draft = np.zeros((B,), bool)
        for b in release:
            rel[check_slot(b)] = True
        for entry in admits:
            b, toks, max_new = entry[0], entry[1], entry[2]
            opts = dict(entry[3]) if len(entry) > 3 and entry[3] else {}
            b = check_slot(b)
            t = self.validate_request(
                toks, max_new, temperature=opts.get("temperature"),
                top_k=opts.get("top_k"), seed=opts.get("seed"))
            adm[b] = True
            prompt[b, :t.size] = t
            plen[b] = t.size
            mnew[b] = max_new
            if opts.get("temperature") is not None:
                temp[b] = float(opts["temperature"])
            if opts.get("top_k") is not None:
                topk[b] = int(opts["top_k"])
            if opts.get("seed") is not None:
                skey[b] = np.asarray(
                    jax.random.PRNGKey(int(opts["seed"])), np.uint32)
            else:
                skey[b] = np.asarray(jax.random.fold_in(
                    self._req_base_key, self._admitted), np.uint32)
            draft[b] = bool(opts.get("draft", self._default_draft()))
            self._admitted += 1
        hits: dict = {}
        pos0 = np.zeros((B,), np.int32)
        if self.paged:
            # all-or-nothing page accounting BEFORE any state mutates:
            # released/recycled slots return their chains, admitted
            # prompts take ceil(plen/page) up front (decode pages grow
            # step by step via reserve_decode_pages).  Two-tier check:
            # worst case (no prefix discount) first; if that fails and
            # the prefix cache is on, re-probe with admit_cost (full
            # pages a live slot already references attach for free) —
            # the same charge model Scheduler._fill_slots gates with.
            recycled = [b for b in range(B) if rel[b] or adm[b]]
            avail = self.allocator.available_pages + sum(
                self.allocator.reclaimable_pages(b) for b in recycled)
            need = sum(self.allocator.pages_for(int(plen[b]))
                       for b in range(B) if adm[b])
            if need > avail and self.prefix is not None:
                need = sum(self.admit_cost(prompt[b, :plen[b]])
                           for b in range(B) if adm[b])
            if need > avail:
                raise RuntimeError(
                    f"admission needs {need} pages, only {avail} "
                    f"available (pool {self.n_pages}); queue instead — "
                    f"Scheduler._fill_slots admits by free pages")
            for b in recycled:
                self._release_slot(b)
            cow_src = np.full((B,), self.n_pages, np.int32)
            cow_dst = np.full((B,), self.n_pages, np.int32)
            any_cow = False
            for b in range(B):
                if not adm[b]:
                    continue
                p = int(plen[b])
                if self.prefix is not None:
                    toks = prompt[b, :p]
                    # cap the hit at plen - 1: the request's first
                    # sampled token needs last-token logits, so at
                    # least one prompt position always prefills
                    hit, full, tail = self.prefix.match(toks, p - 1)
                    if full or tail:
                        self.allocator.share(
                            b, full + ([tail[0]] if tail else []))
                    if tail is not None:
                        # the hit ends mid-page: the slot's first write
                        # (position hit, offset hit % page) lands inside
                        # the matched page — swap in a private copy
                        # before any kernel can write it
                        src, dst = self.allocator.cow(b, len(full))
                        cow_src[b], cow_dst[b] = src, dst
                        any_cow = True
                    pos0[b] = hit
                    hits[b] = hit
                    self._host_prompt[b, :p] = toks
                if not self.allocator.alloc(
                        b, self.allocator.pages_for(p)):
                    raise RuntimeError("page accounting violated its "
                                       "feasibility check")  # unreachable
                self._host_active[b] = True
                self._host_pos[b] = int(pos0[b])
                self._host_plen[b] = p
                self._host_new[b] = int(mnew[b])
            self._table_stale = True
            self._sync_table()
            if any_cow:
                # dispatch the page copy BEFORE _update resets the slot
                # and before any prefill: the data dependence through
                # the donated pool orders the src read ahead of every
                # later write, even if src is evicted and handed to
                # another slot inside this same admission batch
                self.cache = self._copy(self.cache, jnp.asarray(cow_src),
                                        jnp.asarray(cow_dst))
        self.state, self.cache = self._update(
            self.cache, self.state, jnp.asarray(rel), jnp.asarray(adm),
            jnp.asarray(prompt), jnp.asarray(plen), jnp.asarray(mnew),
            jnp.asarray(temp), jnp.asarray(topk), jnp.asarray(skey),
            jnp.asarray(draft), jnp.asarray(pos0))
        return hits

    def _default_draft(self) -> bool:
        """Whether an admission with no explicit `draft` option drafts
        speculatively.  The base engine has no draft model; the
        speculative subclass flips this to True."""
        return False

    def _sync_each_step(self) -> bool:
        """generate(): fetch the done flags after every step and exit
        the loop early.  False here — the base engine emits exactly one
        token per live row per step, so the fixed step count is already
        tight and the static-batch loop stays dispatch-only.  The
        speculative subclass returns True: its per-row stride is
        variable (1..gamma+1 tokens per iteration), so without the
        fetch the loop would keep dispatching full speculative programs
        long after every row finished."""
        return False

    def generate(self, prompts: Sequence[np.ndarray],
                 max_new: int) -> list:
        """Static-batch decode: admit up to n_slots prompts, run to done.

        The whole run is dispatch-only (no host sync inside the loop) —
        except on an OVERSUBSCRIBED paged pool with EOS enabled, where
        each step fetches the done flags: the host page mirror cannot
        see an EOS finish, and without a harvest loop to release the
        slot it would keep growing pages for it until the free list
        spuriously ran dry.  Use scheduler.Scheduler for continuous
        admission instead.
        Returns one int32 array of generated tokens per prompt —
        identical whatever the engine's placement (mesh or not) and,
        with prefill_chunk=0, via the per-token teacher-forcing
        reference path every other configuration is tested against.
        """
        if len(prompts) == 0:
            return []
        if len(prompts) > self.n_slots:
            raise ValueError(f"{len(prompts)} prompts > {self.n_slots} slots")
        hits = self.update_slots(
            release=range(self.n_slots),
            admits=[(i, p, max_new) for i, p in enumerate(prompts)])
        plens = [len(np.reshape(p, -1)) for p in prompts]
        if self.prefill_chunk > 0:
            # chunked prefill emits each slot's first token; decode does
            # the remaining max_new - 1.  Prefix-cache hits shorten a
            # slot's walk: it starts at the hit boundary, and hit <=
            # plen - 1 guarantees at least one chunk always runs
            for i, plen in enumerate(plens):
                left = plen - hits.get(i, 0)
                for _ in range(-(-left // self.prefill_chunk)):
                    self.prefill(i)
            steps = max_new - 1
        else:
            steps = max(plens) + max_new - 1
        sync_done = (self.paged and self.eos_id >= 0
                     and self.n_pages < self.n_slots * self.pages_per_slot)
        early = self._sync_each_step()
        for _ in range(steps):
            self.step()
            if sync_done:
                self._host_active &= ~np.asarray(
                    jax.device_get(self.state.done))
            if early:
                act, done = jax.device_get((self.state.active,
                                            self.state.done))
                if not np.any(np.asarray(act) & ~np.asarray(done)):
                    break
        st = jax.device_get(self.state)
        return [st.out[i, :st.n_gen[i]] for i in range(len(prompts))]

    def score(self, tokens: jax.Array, labels: jax.Array):
        """Teacher-forced NLL of a (B, T) batch: (per-member (K,), ensemble).

        The serving-side face of the Jensen guarantee: the returned
        ensemble NLL is <= the mean member NLL for any members — and
        the quorum-weighted subset keeps the bound, so it holds under
        straggler drop too.  Uses a private cache pool (member-sharded
        like the serving pool when a mesh is set); slot state is
        untouched.  The returned per-member vector is always the global
        (K,), whatever the placement.
        """
        tokens = jnp.asarray(tokens, jnp.int32)
        B, T = tokens.shape
        cache = kv_cache.init_pool(self.cfg, self.n_members, B, T,
                                   mesh=self.mesh)
        if self.cfg.enc_dec:
            cache["enc"] = self._encode_stub(B)
        m_tot = jnp.zeros((self.n_members,), jnp.float32)
        e_tot = jnp.zeros((), jnp.float32)
        for t in range(T):
            m, e, cache = self._score(self.params, cache, tokens[:, t],
                                      jnp.asarray(labels[:, t]), self.quorum)
            m_tot, e_tot = m_tot + m, e_tot + e
        return m_tot / T, e_tot / T

    def swap_params(self, new_stacked_params) -> None:
        """Install a new member stack between iterations — model
        hot-swap, the serving end of the paper's train -> compress ->
        serve loop (every aggregation round publishes a new distilled
        global model; the fleet must pick it up without restarting).

        The new pytree must match the live one exactly (treedef,
        leaf shapes, dtypes): the jitted decode/prefill/score kernels
        key their caches on those, so a conforming swap reuses the SAME
        compiled programs — zero recompiles, gated by
        `benchmarks/serving_bench.py --frontend`.  Under a mesh the new
        stack is re-sharded to the live member placement
        (`member_pspecs`), so the device-side layout is also unchanged.

        The KV pool, page table, and slot state are NOT touched:
        in-flight requests keep decoding through the swap (their
        remaining tokens come from the new weights — drain the slots
        first, e.g. `frontend.Router.rollout`, when each request must
        be served end-to-end by one model version).  K itself is fixed;
        grow/shrink the stack with `checkpoint.store.reshard_members`
        BEFORE swapping.
        """
        old_def, old_shapes = self._param_spec
        new_leaves, new_def = jax.tree_util.tree_flatten(new_stacked_params)
        if old_def != new_def:
            raise ValueError(
                f"swap_params: new param tree structure {new_def} does not "
                f"match the live engine's {old_def}")
        for i, ((oshape, odtype), n) in enumerate(zip(old_shapes,
                                                      new_leaves)):
            if oshape != n.shape or odtype != n.dtype:
                raise ValueError(
                    f"swap_params: leaf {i} is {n.shape}/{n.dtype}, live "
                    f"engine has {oshape}/{odtype} — a mismatched stack "
                    f"would recompile every kernel (use "
                    f"checkpoint.store.reshard_members to change K first)")
        if self.paged:
            # re-derive the absorbed projections from the NEW weights
            # (same leaf shapes as the live tree -> no recompiles)
            new_stacked_params = tf.absorb_mla_params(self.cfg,
                                                      new_stacked_params)
        if self.mesh is None:
            self.params = jax.tree.map(jnp.asarray, new_stacked_params)
        else:
            self.params = jax.device_put(
                new_stacked_params,
                shd.make_shardings(self.mesh,
                                   shd.member_pspecs(new_stacked_params)))
        if self.cfg.enc_dec:
            # the stub encoder context is a function of the params;
            # recompute it so decode reads the new model's encodings
            self.cache["enc"] = self._encode_stub(self.n_slots)
        if self.paged and self.prefix is not None:
            # cached prefix pages hold the OLD model's KV: a round-t
            # prefix must never serve round t+1.  Flush the trie; pages
            # still referenced by in-flight slots are disowned and free
            # on their release (drain first — Router.rollout does —
            # when zero stale pages may survive the swap).
            self.allocator.flush_cache()
        self.swaps_done += 1

    def set_quorum(self, mask: Sequence[float]):
        """0/1 liveness per member; renormalized on-device, no recompile.

        The quorum is a traced (K,) argument of every kernel, so
        dropping a straggler mid-stream recompiles NOTHING and — on the
        mesh path, where the vector is member-sharded like the params —
        reshards nothing either: a dead member's shard keeps computing,
        its vote just carries zero weight in the fused reduction.
        """
        q = ens.quorum_weights(jnp.asarray(mask, jnp.float32))
        if q.shape != (self.n_members,):
            raise ValueError(f"quorum mask wants {self.n_members} entries, "
                             f"got {q.shape}")
        if self.mesh is not None:
            q = jax.device_put(
                q, NamedSharding(self.mesh, P(shd.MEMBER_AXIS)))
        self.quorum = q

    def cache_bytes(self) -> int:
        """PER-DEVICE bytes of the cache pool (capacity telemetry).

        Under a member-sharded pool each device holds K/M members'
        planes, so this reports the global figure divided by the mesh
        member-axis size — the number a chip actually budgets.  On the
        unsharded reference path per-device == global.
        """
        return kv_cache.pool_bytes(self.cache, per_device=True)
