"""Shared tiny-config builders for tests."""
import jax
import numpy as np

from repro.configs.base import ModelConfig

BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab_size=257, head_pad_multiple=1, vocab_pad_multiple=1,
            dtype="float32", remat=False)


def tiny(family="dense", **kw):
    if family == "hybrid_moe":     # Granite-4.0-H's block, 2 of 16 experts
        from repro.configs.granite_4_0_h_small import SMOKE_CONFIG
        return SMOKE_CONFIG.with_(**dict(BASE, n_layers=3, d_ff=32, **kw))
    base = dict(BASE)
    if family == "moe":
        base.update(n_experts=4, top_k=2)
    if family in ("ssm", "hybrid"):
        base.update(ssm_state=16, ssm_headdim=16, ssm_chunk=8)
    if family == "hybrid":
        base.update(n_layers=3, hybrid_ssm_per_block=1)
    if family == "encdec":
        base.update(n_enc_layers=2, max_source_len=8)
    if family == "vlm":
        base.update(n_img_tokens=4)
    base.update(kw)
    return ModelConfig(name=f"tiny-{family}", family=family, **base)


class GoldenPredictor:
    """Deterministic, model-free PredictorAdapter for golden-container tests.

    Next-token logits are a fixed (V, V) table indexed by the previous
    token, so both the teacher-forced and incremental scoring paths
    produce bit-identical distributions with no jitted model involved.
    The table is well-separated (scaled normals) so CDF quantization is
    robust to float rounding differences across BLAS builds.
    """

    def __init__(self, vocab_size=64, seed=0):
        self.vocab_size = int(vocab_size)
        self.bos_id = self.vocab_size - 1
        rng = np.random.default_rng(seed)
        self._table = (rng.standard_normal(
            (self.vocab_size, self.vocab_size)) * 2.0).astype(np.float32)

    def score_chunks(self, tokens):
        tokens = np.asarray(tokens, np.int32)
        prev = np.concatenate(
            [np.full((tokens.shape[0], 1), self.bos_id, np.int32),
             tokens[:, :-1]], axis=1)
        return self._table[prev]

    def begin_decode(self, batch):
        return None

    def decode_step(self, state, prev_tokens):
        return self._table[np.asarray(prev_tokens, np.int32)], state

    # speculative decode hooks: the model is stateless (logits depend on
    # the previous token only), so verify is a pure table gather and
    # rollback is the identity
    def verify_steps(self, state, seq):
        return self._table[np.asarray(seq, np.int32)], state

    def rollback(self, snapshots, accepted):
        return snapshots

    # prefix-cache hooks (v6): state is None, so a per-lane snapshot is
    # trivially empty and restore is the identity — which lets scheduler
    # tests exercise the radix-cache bookkeeping (hits, skipped prefill
    # steps) without a jitted model
    def snapshot_slot(self, state, lane):
        return ("golden-snap",)

    def restore_slot(self, state, snapshot, mask):
        return state


def golden_tokens(n=45, seed=1234, vocab=63):
    """The fixed token stream the golden containers were built from.
    Uniform random — the GoldenPredictor table model genuinely *loses*
    to raw store on this stream (~9.5 model bits/token vs 8 packed), so
    it doubles as the router's adversarial input."""
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def golden_self_tokens(n=45, seed=5678, vocab=64):
    """Tokens softmax-sampled from the GoldenPredictor's own table — the
    stream that model predicts well, i.e. the paper's LLM-generated-text
    regime where the entropy path wins and the router must keep it."""
    pred = GoldenPredictor(vocab_size=vocab)
    rng = np.random.default_rng(seed)
    out = np.empty(n, np.int32)
    prev = pred.bos_id
    for i in range(n):
        logits = pred._table[prev].astype(np.float64)
        p = np.exp(logits - logits.max())
        prev = out[i] = rng.choice(vocab, p=p / p.sum())
    return out


def golden_mixed_tokens():
    """The fixed mixed-regime stream behind the v5 routed golden: at
    chunk_size 16 it splits into 4 chunks alternating model-friendly
    (self-generated -> rans tag) and adversarial (uniform random -> raw
    tag), the last one a 13-token tail."""
    return np.concatenate([golden_self_tokens(16, seed=11),
                           golden_tokens(16, seed=22),
                           golden_self_tokens(16, seed=33),
                           golden_tokens(13, seed=44)])


def golden_text_tokens(n=140, vocab=63):
    """Highly repetitive 'text-like' stream: a dictionary codec (lzma /
    zstd) beats both raw store and the table model on it — the forced-
    fallback goldens use it so the fallback codec actually wins."""
    motif = np.array([5, 6, 7, 5, 6, 7, 9, 9, 5, 6], np.int32) % vocab
    return np.tile(motif, n // motif.size + 1)[:n].astype(np.int32)


def rand_batch(cfg, B=2, S=16, key=0):
    import jax.numpy as jnp
    k = jax.random.PRNGKey(key)
    batch = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["img_embeds"] = jax.random.normal(k, (B, cfg.n_img_tokens,
                                                    cfg.d_model))
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(k, (B, 8, cfg.d_model))
    return batch
