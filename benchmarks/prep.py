"""Build (and cache) the measured-experiment assets: trained byte-level
predictor LMs and the human-like / LLM-generated corpora.

Everything lands in results/bench_cache/ keyed by config; re-runs are
no-ops. The predictors are the paper's "LLMs" scaled down to a few million
parameters (same dense llama-family; see configs/paper_predictors.py).
"""
from __future__ import annotations

import pathlib
import time
import zlib

import numpy as np

CACHE = pathlib.Path(__file__).resolve().parents[1] / "results" / "bench_cache"

PREDICTORS = ("pred-tiny", "pred-small", "pred-base")
TRAIN_STEPS = {"pred-tiny": 260, "pred-small": 260, "pred-base": 220,
               "pred-large": 160}
DOMAINS = ("wiki", "code", "math", "clinical", "web", "science", "novel",
           "article")


def _cfg(name):
    from repro.configs import paper_predictors as pp
    return {"pred-tiny": pp.PRED_TINY, "pred-small": pp.PRED_SMALL,
            "pred-base": pp.PRED_BASE, "pred-large": pp.PRED_LARGE}[name]


def train_predictor(name: str, *, steps=None, seed=0, domain_mix=DOMAINS,
                    corpus_bytes=1 << 20, log=print):
    """Train a predictor on a mixed human-like corpus; cache the params."""
    import jax
    from repro.data.synthetic import human_like
    from repro.data.tokenizer import encode
    from repro.data.pipeline import TokenPipeline
    from repro.launch.mesh import local_mesh
    from repro.models.schema import init_params
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.train_loop import make_train_step
    from repro.train.checkpoint import restore_latest, save_checkpoint

    cfg = _cfg(name)
    steps = steps or TRAIN_STEPS[name]
    ckpt_dir = CACHE / f"{name}-s{seed}"
    params_like = init_params(cfg, jax.random.PRNGKey(seed))
    restored, step = restore_latest(ckpt_dir, {"params": params_like})
    if restored is not None and step >= steps:
        return restored["params"], cfg

    corpus = b"".join(
        human_like(d, corpus_bytes // len(domain_mix), seed=seed + i)
        for i, d in enumerate(domain_mix))
    toks = encode(corpus)
    pipe = TokenPipeline(toks, global_batch=16, seq_len=192, seed=seed)
    opt = AdamWConfig(learning_rate=3e-3, warmup_steps=20, total_steps=steps)
    params = params_like
    opt_state = init_opt_state(params, opt)
    step_fn = make_train_step(cfg, local_mesh(), opt=opt, global_batch=16)
    t0 = time.time()
    for s in range(steps):
        batch = {"tokens": pipe.global_batch_array(s)}
        params, opt_state, m = step_fn(params, opt_state, batch)
        if s % 50 == 0:
            log(f"  [{name}] step {s} loss {float(m['loss']):.3f} "
                f"({time.time()-t0:.0f}s)")
    save_checkpoint(ckpt_dir, steps, {"params": params})
    log(f"  [{name}] trained {steps} steps, final loss "
        f"{float(m['loss']):.3f} in {time.time()-t0:.0f}s")
    return params, cfg


def predictor(name: str, *, seed=0):
    """Trained ModelPredictor (cached)."""
    from repro.serve.engine import ModelPredictor
    from repro.data.tokenizer import BOS_ID
    params, cfg = train_predictor(name, seed=seed)
    return ModelPredictor(params, cfg, bos_id=BOS_ID)


def llm_dataset(domain: str, n_bytes: int = 6144, *, gen_model="pred-base",
                temperature=0.55, seed=0, doc_len=384) -> bytes:
    """Cached 'LLM-generated' dataset: the gen_model continues a domain
    prompt — the paper's LLM-generated text, per category.

    * temperature 0.55: scaled to the paper's predictability regime — its
      1-14B generators emit ~0.35-0.55 bits/byte under their own scoring;
      a ~5M predictor needs a lower temperature to land in a comparable
      regime.
    * fixed `doc_len` per generated document, corpus = concatenation of
      independent documents (a real corpus is many documents; one long
      stream from a small model drifts off-distribution and the measured
      "dataset scale" effect becomes generator drift, not compressor
      behaviour).
    """
    path = CACHE / (f"gen3-{gen_model}-{domain}-{n_bytes}-t{temperature}"
                    f"-d{doc_len}-s{seed}.bin")
    if path.exists():
        return path.read_bytes()
    from repro.data.synthetic import human_like
    from repro.data.tokenizer import encode
    pred = predictor(gen_model, seed=0)
    n_docs = -(-n_bytes // doc_len)
    plen = 128
    # DISTINCT prompt per document (a shared prompt is dictionary-compressor
    # candy and unrepresentative of a real generated corpus)
    prompts = np.stack([encode(human_like(domain, plen, seed=seed + 77 + i))
                        for i in range(n_docs)])
    gen_len = doc_len - plen
    toks = pred.generate(gen_len, batch=n_docs, temperature=temperature,
                         seed=seed + zlib.crc32(domain.encode()) % 1000,
                         prompt=prompts,
                         vocab_limit=256)
    # document = prompt + continuation: the compressor scores the
    # continuation with the same context the generator saw
    docs = np.concatenate([prompts, toks], axis=1)
    data = docs.ravel().astype(np.uint8).tobytes()[:n_bytes]
    CACHE.mkdir(parents=True, exist_ok=True)
    path.write_bytes(data)
    return data


def human_dataset(domain: str, n_bytes: int = 6144, seed: int = 0) -> bytes:
    from repro.data.synthetic import human_like
    return human_like(domain, n_bytes, seed=seed)


def build_all(log=print):
    for name in PREDICTORS:
        log(f"[prep] predictor {name}")
        train_predictor(name, log=log)
    for d in DOMAINS:
        log(f"[prep] dataset {d}")
        llm_dataset(d)


if __name__ == "__main__":
    build_all()
