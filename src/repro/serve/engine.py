"""Serving engine: batched prefill (scoring) + lock-step decode.

This is the inference side of the paper's system. `ModelPredictor`
implements core.compressor.PredictorAdapter over any model-zoo config:

  * score_chunks — one jitted teacher-forced forward over (B, C) chunks
    (prefill-shaped).
  * decode loop — jitted single-token step. For the families that update
    their cache in place (``model_api.IN_PLACE_DECODE``) the step's cache
    argument is donated: the new K/V rows land in the buffers it read.
    ``decode_step`` hands those buffers back into the state it was given,
    which keeps its position and so still steps as it did, in place of the
    state returned. The other families' steps write a new cache beside the
    one they read.
    The step's logits stay on the device: ``decode_step`` returns the
    program's ``jax.Array``, which the service's CDF program reads in
    place, and only callers that need host logits copy them.

The BOS convention: the model input for chunk tokens x_0..x_{C-1} is
[BOS, x_0, .., x_{C-2}], so logits[t] parameterizes P(x_t | x_<t) with a
fresh context per chunk — exactly the paper's chunked setup (§5.4).

For MoE models both paths run dropless dispatch (see models/moe.py) so
scoring and decoding produce bit-identical distributions — the lossless
requirement. A family whose decode program also counts its own work
(``model_api.STEP_STATS``) returns those counters as one more output;
``decode_step`` keeps them on the device in ``step_stats`` for the
service to fetch with the step's CDFs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.models import api as model_api


def _count_bytes(counter: str, host: np.ndarray) -> None:
    """Add a host array's bytes to ``counter`` in the current registry
    (the open span's, else the process default) while it is enabled."""
    reg = obs.trace.current_registry()
    if reg.enabled:
        reg.counter(counter).inc(host.nbytes)


class ModelPredictor:
    """PredictorAdapter over the model zoo (single-host execution)."""

    def __init__(self, params, cfg: ModelConfig, *, bos_id: int | None = None,
                 extra_batch: dict | None = None):
        self.params = params
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self.bos_id = bos_id if bos_id is not None else cfg.vocab_size - 1
        self.extra_batch = extra_batch or {}
        fam_kw = {"dropless": True} if cfg.family in model_api.DROPLESS \
            else {}
        stats_kw = {"stats": True} if cfg.family in model_api.STEP_STATS \
            else {}
        self.step_stats = None
        self._in_place = cfg.family in model_api.IN_PLACE_DECODE

        # jax.named_scope labels below mirror the host-span names (minus
        # the dots XProf dislikes) so a captured device trace interleaves
        # with the obs.trace host timeline under one vocabulary.
        @jax.jit
        def _score(params, tokens, extra):
            with jax.named_scope("model_score"):
                inp = jnp.concatenate(
                    [jnp.full((tokens.shape[0], 1), self.bos_id,
                              tokens.dtype),
                     tokens[:, :-1]], axis=1)
                batch = {"tokens": inp, **extra}
                logits = model_api.forward(params, cfg, batch, **fam_kw)
                return logits[..., :cfg.vocab_size]

        @partial(jax.jit, donate_argnums=(1,) if self._in_place else ())
        def _decode(params, cache, prev, extra):
            with jax.named_scope("model_decode_step"):
                logits, cache, *stats = model_api.decode_step(
                    params, cfg, cache, prev, **fam_kw, **stats_kw)
                return (logits[..., :cfg.vocab_size], cache, *stats)

        @jax.jit
        def _score_ctx(params, tokens, prefix, extra):
            with jax.named_scope("model_score_prefix"):
                inp = jnp.concatenate(
                    [jnp.full((tokens.shape[0], 1), self.bos_id,
                              tokens.dtype),
                     prefix, tokens[:, :-1]], axis=1)
                batch = {"tokens": inp, **extra}
                logits = model_api.forward(params, cfg, batch, **fam_kw)
                return logits[:, prefix.shape[1]:, :cfg.vocab_size]

        @jax.jit
        def _prefill(params, cache, prefix, extra):
            """Consume [BOS, prefix[:, :-1]] through the decode-step
            program in one dispatch. Each scanned step IS the lock-step
            decoder's own jitted computation (same program, same reduction
            order — the _verify argument), so the resulting cache is
            bit-identical to P sequential decode_step calls. The caller
            then feeds prefix[:, -1] as the first decode input."""
            del extra
            inp = jnp.concatenate(
                [jnp.full((prefix.shape[0], 1), self.bos_id, prefix.dtype),
                 prefix[:, :-1]], axis=1)

            def step(c, tok):
                with jax.named_scope("model_prefill_step"):
                    _, c2 = model_api.decode_step(params, cfg, c, tok,
                                                  **fam_kw)
                    return c2, None

            cache, _ = jax.lax.scan(step, cache, jnp.swapaxes(inp, 0, 1))
            return cache

        @jax.jit
        def _verify(params, cache, seq, extra):
            """Score T = seq.shape[1] positions in ONE dispatch by scanning
            the decode-step program, emitting the post-step cache after
            every input. Because each step IS the lock-step decoder's own
            jitted computation (same program, same reduction order), the
            logits are bit-identical to T sequential decode_step calls —
            the property speculative decompression stands on (DESIGN.md
            §9). Memory: the stacked snapshots cost (T+1)x the cache — the
            price of masked per-lane rollback in one gather."""
            del extra

            def step(c, tok):
                with jax.named_scope("model_verify_step"):
                    lg, c2 = model_api.decode_step(params, cfg, c, tok,
                                                   **fam_kw)
                    return c2, (lg[..., :cfg.vocab_size], c2)

            _, (logits, snaps) = jax.lax.scan(step, cache,
                                              jnp.swapaxes(seq, 0, 1))
            # snapshot 0 = the entering cache (0 inputs consumed), so a
            # rollback index is simply "#inputs this lane keeps"
            snaps = jax.tree_util.tree_map(
                lambda s0, st: jnp.concatenate([s0[None], st], axis=0),
                cache, snaps)
            return jnp.swapaxes(logits, 0, 1), snaps

        @jax.jit
        def _rollback(snaps, acc):
            """Per-lane masked cache restore: lane b resumes from the
            snapshot taken after it consumed acc[b] of the verify inputs
            (reset_slots-style — a runtime gather, no recompilation).
            Cache leaves are (L, B, ...) batch-axis-1 except 'pos' (B,);
            encdec cross-attn conditioning (xk/xv) is constant across
            steps, so any snapshot of it is the value itself."""
            def leaf(path, x):
                name = path[-1].key if hasattr(path[-1], "key") else ""
                if name in ("xk", "xv"):
                    return x[0]
                ba = 1 if name == "pos" else 2     # batch axis in (T+1, ...)
                xm = jnp.moveaxis(x, ba, 1)        # (T+1, B, rest...)
                out = jax.vmap(lambda col, a: col[a],
                               in_axes=(1, 0))(xm, acc)      # (B, rest...)
                return jnp.moveaxis(out, 0, ba - 1)
            return jax.tree_util.tree_map_with_path(leaf, snaps)

        @jax.jit
        def _snapshot(cache, lane):
            """Copy one cache lane out as a standalone snapshot (the radix
            prefix cache's stored value). Leaves are (L, B, ...) batch-
            axis-1 except 'pos' (B,); encdec cross-attn conditioning
            (xk/xv) is per-job, not per-slot context, so it stays whole
            and restore leaves the target's own value in place."""
            def leaf(path, x):
                name = path[-1].key if hasattr(path[-1], "key") else ""
                if name in ("xk", "xv"):
                    return x
                if name == "pos":
                    return x[lane]
                return jnp.take(x, lane, axis=1)
            return jax.tree_util.tree_map_with_path(leaf, cache)

        @jax.jit
        def _restore(cache, snap, mask):
            """Broadcast a single-lane snapshot into every cache lane
            selected by mask (B,) bool — the prefix-cache-hit path: the
            slot resumes from the stored post-prefill state instead of
            re-running prefill. Runtime mask, no recompilation."""
            def leaf(path, x, s):
                name = path[-1].key if hasattr(path[-1], "key") else ""
                if name in ("xk", "xv"):
                    return x
                if name == "pos":
                    return jnp.where(mask, s, x).astype(x.dtype)
                shape = [1] * x.ndim
                shape[1] = mask.shape[0]
                return jnp.where(mask.reshape(shape),
                                 jnp.expand_dims(s, 1), x)
            return jax.tree_util.tree_map_with_path(leaf, cache, snap)

        @jax.jit
        def _reset(cache, mask):
            """Zero the cache lanes selected by mask (B,) bool — per-slot
            fresh context for the continuous-batching scheduler. 'pos'
            lanes return to 0; recurrent state (SSM conv/state) MUST be
            zeroed (it is the context); attention K/V lanes are zeroed
            for hygiene (the per-lane causal mask already hides them);
            encdec cross-attn caches (xk/xv) are per-job conditioning and
            survive the reset."""
            def leaf(path, x):
                name = path[-1].key if hasattr(path[-1], "key") else ""
                if name in ("xk", "xv"):
                    return x
                if name == "pos":
                    return jnp.where(mask, 0, x).astype(x.dtype)
                # every other cache leaf is (L, B, ...) — batch on axis 1
                shape = [1] * x.ndim
                shape[1] = mask.shape[0]
                return jnp.where(mask.reshape(shape), jnp.zeros((), x.dtype),
                                 x)
            return jax.tree_util.tree_map_with_path(leaf, cache)

        self._score = _score
        self._score_ctx = _score_ctx
        self._prefill = _prefill
        self._decode = _decode
        self._verify = _verify
        self._rollback = _rollback
        self._snapshot = _snapshot
        self._restore = _restore
        self._reset = _reset

    # --------------------------------------------------- PredictorAdapter
    def score_chunks(self, tokens: np.ndarray,
                     prefix: np.ndarray | None = None) -> np.ndarray:
        """Teacher-forced logits for (B, C) chunks. With ``prefix``
        (B, P) or (P,), position t is scored given [prefix, x_<t] instead
        of a fresh context — the v6 carried/shared-context scorer."""
        with obs.span("model.score"):
            tokens = jnp.asarray(tokens, jnp.int32)
            if prefix is None:
                return np.asarray(
                    self._score(self.params, tokens, self.extra_batch))
            prefix = jnp.asarray(prefix, jnp.int32)
            if prefix.ndim == 1:
                prefix = jnp.broadcast_to(
                    prefix[None], (tokens.shape[0], prefix.shape[0]))
            return np.asarray(self._score_ctx(self.params, tokens, prefix,
                                              self.extra_batch))

    def begin_decode(self, batch: int, prefix: np.ndarray | None = None):
        """Fresh decode cache for ``batch`` lanes. With ``prefix`` (B, P)
        or (P,), the cache has consumed [BOS, prefix[:, :-1]] in one
        scanned dispatch (bit-identical to sequential decode_step calls);
        the caller feeds prefix[:, -1] as the first decode_step input."""
        max_len = getattr(self, "_decode_max_len", 1024)
        cache = model_api.init_cache(self.cfg, batch, max_len)
        if self.cfg.family == "encdec" and "frames" in self.extra_batch:
            from repro.models.encdec import precompute_cross_kv
            frames = self.extra_batch["frames"]
            if frames.shape[0] != batch:
                frames = jnp.broadcast_to(
                    frames[:1], (batch,) + frames.shape[1:])
            cache["xk"], cache["xv"] = precompute_cross_kv(
                self.params, self.cfg, frames)
        if prefix is not None:
            prefix = jnp.asarray(prefix, jnp.int32)
            if prefix.ndim == 1:
                prefix = jnp.broadcast_to(prefix[None],
                                          (batch, prefix.shape[0]))
            with obs.span("model.prefill"):
                cache = self._prefill(self.params, cache, prefix,
                                      self.extra_batch)
        return cache

    def set_decode_len(self, n: int):
        self._decode_max_len = int(n)

    def decode_step(self, state, prev_tokens: np.ndarray):
        """One decode step: (B,) previous tokens -> (B, V) logits and the
        next state. For a family in ``model_api.IN_PLACE_DECODE`` the step
        donates ``state``'s K/V buffers and writes its rows into them; they
        go back into ``state`` with its own ``pos``, so ``state`` still
        steps exactly as before (a step rewrites each lane's row at ``pos``
        before it reads it). The two states then share those buffers:
        step one of them, which consumes both.

        The logits are the ``jax.Array`` the program writes: nothing waits
        for it and nothing is copied to the host, so the service hands
        them straight to its CDF program. A caller that reads them on the
        host converts them (``np.asarray``). The step's counters, for a
        family that returns them, stay on the device in ``step_stats``
        until the next step."""
        prev = np.asarray(prev_tokens, np.int32)
        _count_bytes("transfer.h2d_bytes", prev)
        logits, new, *stats = self._decode(
            self.params, state, jnp.asarray(prev), self.extra_batch)
        if self._in_place:      # state's K/V and pos buffers were donated
            state.update({k: v for k, v in new.items() if k != "pos"},
                         pos=new["pos"] - 1)
        self.step_stats = stats[0] if stats else None
        return logits, new

    def verify_steps(self, state, seq: np.ndarray):
        """Speculative-decode verify program: score seq (B, T) — column 0
        is each lane's previous token, columns 1..T-1 its drafted
        continuation — in one jitted dispatch. Returns (logits (B, T, V)
        bit-identical to T lock-step decode_step calls, snapshots) where
        ``snapshots`` is the opaque stacked-cache value ``rollback``
        consumes."""
        with obs.span("model.verify"):
            logits, snaps = self._verify(self.params, state,
                                         jnp.asarray(seq, jnp.int32),
                                         self.extra_batch)
            return np.asarray(logits), snaps

    def rollback(self, snapshots, accepted: np.ndarray):
        """Restore each lane's cache to the state after it consumed
        ``accepted[b]`` verify inputs (0 = the pre-verify cache) — the
        speculative decoder's masked per-lane rewind. One jitted gather."""
        with obs.span("model.rollback"):
            return self._rollback(snapshots,
                                  jnp.asarray(accepted, jnp.int32))

    def snapshot_slot(self, state, lane: int):
        """Copy cache lane ``lane`` out as a standalone snapshot — the
        value a radix prefix cache stores for a prefilled shared prefix.
        One jitted gather; the live cache is untouched."""
        with obs.span("model.snapshot_slot"):
            return self._snapshot(state, jnp.asarray(lane, jnp.int32))

    def restore_slot(self, state, snapshot, mask: np.ndarray):
        """Broadcast ``snapshot`` (from snapshot_slot) into every cache
        lane selected by ``mask`` (B,) bool — the prefix-cache-hit path
        that replaces re-prefilling those lanes. One jitted select."""
        with obs.span("model.restore_slot"):
            return self._restore(state, snapshot, jnp.asarray(mask, bool))

    def reset_slots(self, state, mask: np.ndarray):
        """Reset the cache lanes selected by ``mask`` (B,) bool to a fresh
        context (pos 0, zero recurrent state) without touching the other
        lanes — the slot-refill primitive of the continuous-batching
        scheduler (repro.service). One jitted call, no recompilation:
        the mask is a runtime input."""
        with obs.span("model.reset_slots"):
            mask = np.asarray(mask, bool)
            _count_bytes("transfer.h2d_bytes", mask)
            return self._reset(state, jnp.asarray(mask))

    # ----------------------------------------------------------- sampling
    def generate(self, n_tokens: int, batch: int = 1, *, temperature=1.0,
                 top_k: int = 0, seed: int = 0, prompt=None,
                 vocab_limit: int = 0):
        """Autoregressive sampling — used to create 'LLM-generated' corpora
        for the paper's experiments. vocab_limit > 0 restricts sampling to
        ids < vocab_limit (e.g. 256 for raw bytes, excluding PAD/BOS)."""
        key = jax.random.PRNGKey(seed)
        plen = 0 if prompt is None else np.asarray(prompt).shape[-1]
        self.set_decode_len(max(n_tokens, 16) + plen)
        cache = self.begin_decode(batch)
        prev = np.full((batch,), self.bos_id, np.int32)
        if prompt is not None:
            prompt = np.asarray(prompt, np.int32)
            if prompt.ndim == 1:  # shared prompt
                prompt = np.tile(prompt, (batch, 1))
            for t in range(prompt.shape[1]):
                _, cache = self.decode_step(cache, prev)
                prev = prompt[:, t]
        out = np.zeros((batch, n_tokens), np.int32)
        for t in range(n_tokens):
            logits, cache = self.decode_step(cache, prev)
            key, sub = jax.random.split(key)
            lg = jnp.asarray(logits) / max(temperature, 1e-4)
            if vocab_limit:
                lg = jnp.where(jnp.arange(lg.shape[-1]) < vocab_limit,
                               lg, -1e30)
            if top_k:
                vals, idx = jax.lax.top_k(lg, top_k)
                choice = jax.random.categorical(sub, vals, axis=-1)
                tok = jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]
            else:
                tok = jax.random.categorical(sub, lg, axis=-1)
            prev = np.asarray(tok, np.int32)
            out[:, t] = prev
        return out
