"""Continuous-batching compression service: correctness and scheduling.

The load-bearing claims under test:

* a ragged workload (jobs with chunk counts 1..2B, partial final chunks)
  round-trips bit-exactly through the slot scheduler;
* service-compressed containers are byte-identical to LLMCompressor's
  v4 output and cross-decode with the grouped path in both directions,
  including at a *different* slot count than the encoder's batch;
* per-slot cache reset (serve/engine.reset_slots) is bit-exact with a
  fresh cache while neighbour lanes are mid-stream, for every cached
  model family;
* the scheduler spends fewer model steps than the naive grouped decoder
  on ragged traffic (the subsystem's reason to exist);
* corrupt streams and mismatched configs fail loudly, at submit time
  where possible.
"""
import numpy as np
import pytest

import jax

from helpers import GoldenPredictor, golden_tokens, tiny
from repro.core import ContainerError, LLMCompressor
from repro.models import init_params
from repro.serve.engine import ModelPredictor
from repro.service import CompressionService, SlotScheduler
from repro.service.session import COMPRESS, ChunkTask, Job


def _golden_service(slots=4, chunk=16, topk=8, **kw):
    return CompressionService(GoldenPredictor(), slots=slots,
                              chunk_size=chunk, topk=topk, **kw)


def _golden_compressor(chunk=16, topk=8, **kw):
    return LLMCompressor(GoldenPredictor(), chunk_size=chunk, topk=topk,
                         decode_batch=4, **kw)


def _model_pred(family="dense"):
    cfg = tiny(family, vocab_size=258)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return ModelPredictor(params, cfg, bos_id=257)


# ------------------------------------------------------------ golden-model
def test_service_compress_matches_grouped_v4_bytes():
    """The scheduler's out-of-order, slot-flushed encoder must produce the
    exact container the lock-step grouped compressor writes."""
    toks = golden_tokens(100)
    blob_svc, stats = _golden_service().submit_compress(toks).result()
    blob_ref, _ = _golden_compressor(container_version=4).compress(toks)
    assert blob_svc == blob_ref
    assert stats.n_tokens == toks.size
    assert stats.payload_bytes + stats.header_bytes == len(blob_svc)


def test_v4_records_encode_batch():
    """The v4 footer records the encoder's lane count — the batch shape a
    decoder must run the model program at for bit-exact logits on
    non-batch-invariant (real) models. Advisory for the batch-invariant
    GoldenPredictor, load-bearing for production models (the CLI defaults
    its decode slot count to this field)."""
    from repro.core import read_index
    blob, _ = _golden_service(slots=5, chunk=16) \
        .submit_compress(golden_tokens(40)).result()
    assert read_index(blob).encode_batch == 5      # service: always slots
    blob4, _ = _golden_compressor(container_version=4) \
        .compress(golden_tokens(40))               # 3 chunks < decode_batch
    assert read_index(blob4).encode_batch == 3     # min(4, n_chunks)


def test_ragged_workload_bit_exact():
    """Acceptance: jobs with chunk counts 1..2B (B=4 slots) — including
    sub-chunk and partial-final-chunk jobs — all round-trip losslessly
    through one shared slot machine."""
    svc = _golden_service(slots=4, chunk=16)
    comp = _golden_compressor()
    rng = np.random.default_rng(0)
    sizes = [1, 7, 16, 33, 100, 55, 128, 17]        # 1..8 chunks at C=16
    datas = [rng.integers(0, 63, n).astype(np.int32) for n in sizes]
    handles = [svc.submit_compress(d, priority=i % 3)
               for i, d in enumerate(datas)]
    blobs = [h.result()[0] for h in handles]
    dec_handles = [svc.submit_decompress(b) for b in blobs]
    for d, b, h in zip(datas, blobs, dec_handles):
        assert np.array_equal(h.result(), d)
        assert np.array_equal(comp.decompress(b), d)
    assert svc.stats.chunks_completed == 2 * sum(-(-n // 16) for n in sizes)


def test_mixed_compress_decompress_same_batch():
    """Compress and decompress jobs interleave in the same model steps."""
    svc = _golden_service()
    rng = np.random.default_rng(1)
    toks = golden_tokens(90)
    blob, _ = _golden_compressor(container_version=4).compress(toks)
    d1 = rng.integers(0, 63, 70).astype(np.int32)
    hc = svc.submit_compress(d1)
    hd = svc.submit_decompress(blob)
    # both queued before any result is pulled: they share the batch
    assert np.array_equal(hd.result(), toks)
    blob1, _ = hc.result()
    assert np.array_equal(svc.submit_decompress(blob1).result(), d1)


def test_full_vocab_path_roundtrip():
    svc = _golden_service(slots=3, chunk=10, topk=0)
    rng = np.random.default_rng(2)
    d = rng.integers(0, 63, 47).astype(np.int32)
    blob, _ = svc.submit_compress(d).result()
    assert np.array_equal(svc.submit_decompress(blob).result(), d)


def test_empty_and_tiny_jobs():
    svc = _golden_service()
    h0 = svc.submit_compress(np.zeros(0, np.int32))
    blob0, stats0 = h0.result()
    assert stats0.n_tokens == 0
    assert np.array_equal(svc.submit_decompress(blob0).result(),
                          np.zeros(0, np.int32))
    h1 = svc.submit_compress(np.array([5], np.int32))
    blob1, _ = h1.result()
    assert np.array_equal(svc.submit_decompress(blob1).result(),
                          np.array([5], np.int32))


def test_occupancy_zero_without_steps():
    """SchedulerStats.occupancy on a scheduler that never stepped (or a
    service whose only jobs resolved at submit) is 0.0 — regression for
    the ZeroDivisionError when lane_steps == 0."""
    from repro.service.scheduler import SchedulerStats
    assert SchedulerStats().occupancy == 0.0
    svc = _golden_service()
    assert svc.stats.occupancy == 0.0           # no traffic at all
    blob, _ = svc.submit_compress(np.zeros(0, np.int32)).result()
    svc.submit_decompress(blob).result()        # resolved at submit
    assert svc.stats.model_steps == 0
    assert svc.stats.occupancy == 0.0


def test_legacy_ac_container_decodes_eagerly():
    toks = golden_tokens(60)
    ac_blob, _ = _golden_compressor(codec="ac").compress(toks)
    svc = _golden_service()
    h = svc.submit_decompress(ac_blob)
    assert h.done()                      # grouped path, resolved at submit
    assert np.array_equal(h.result(), toks)


def test_priority_orders_queue():
    """Lower priority value runs first: with a single slot, a later
    high-priority job completes before an earlier low-priority one."""
    sched = SlotScheduler(GoldenPredictor(), n_slots=1, chunk_size=8,
                          topk=8)
    order = []

    def mk(tag, seed):
        job = Job(0, COMPRESS, 0, 1, 8, lambda streams: order.append(tag))
        return ChunkTask(job, 0, COMPRESS, 8,
                         tokens=golden_tokens(8, seed=seed))
    sched.submit(mk("low", 11), priority=5)
    sched.submit(mk("high", 22), priority=-5)
    sched.run()
    assert order == ["high", "low"]


class CountingPredictor(GoldenPredictor):
    """GoldenPredictor that counts decode_step invocations."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.n_steps = 0

    def decode_step(self, state, prev_tokens):
        self.n_steps += 1
        return super().decode_step(state, prev_tokens)


def test_scheduler_beats_grouped_steps_on_ragged():
    """The reason the subsystem exists: on ragged jobs the grouped
    decoder runs each job's groups to valid.max() with idle lanes; the
    slot machine refills immediately and spends fewer model steps."""
    rng = np.random.default_rng(3)
    C, B = 16, 4
    sizes = [1 + int(rng.integers(0, 2 * B * C)) for _ in range(12)]
    datas = [rng.integers(0, 63, n).astype(np.int32) for n in sizes]
    pred = CountingPredictor()
    comp = LLMCompressor(pred, chunk_size=C, topk=8, decode_batch=B,
                         container_version=4)
    blobs = [comp.compress(d)[0] for d in datas]
    pred.n_steps = 0
    for b, d in zip(blobs, datas):          # naive: one grouped job at a time
        assert np.array_equal(comp.decompress(b), d)
    naive_steps = pred.n_steps
    svc = CompressionService(pred, slots=B, chunk_size=C, topk=8)
    handles = [svc.submit_decompress(b) for b in blobs]
    for h, d in zip(handles, datas):
        assert np.array_equal(h.result(), d)
    assert svc.stats.model_steps < naive_steps, \
        (svc.stats.model_steps, naive_steps)
    assert svc.stats.occupancy > 0.75


# -------------------------------------------------------------- real model
@pytest.mark.parametrize("family", ["dense", "hybrid", "hybrid_moe"])
def test_slot_reset_bit_exact_mid_stream(family):
    """reset_slots on a mid-stream batch reproduces fresh-cache logits
    bit-exactly on the reset lanes — the primitive continuous batching
    stands on."""
    pred = _model_pred(family)
    pred.set_decode_len(8)
    B = 4
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, (B, 8)).astype(np.int32)
    cache = pred.begin_decode(B)
    prev = np.full((B,), 257, np.int32)
    ref = []
    for t in range(5):
        lg, cache = pred.decode_step(cache, prev)
        ref.append(lg)
        prev = toks[:, t]
    cache = pred.begin_decode(B)
    prev = np.full((B,), 257, np.int32)
    for t in range(3):
        lg, cache = pred.decode_step(cache, prev)
        prev = toks[:, t]
    mask = np.array([False, True, True, False])
    cache = pred.reset_slots(cache, mask)
    prev = np.where(mask, 257, prev).astype(np.int32)
    for t in range(5):
        lg, cache = pred.decode_step(cache, prev)
        assert np.array_equal(lg[1], ref[t][1])
        assert np.array_equal(lg[2], ref[t][2])
        prev = np.where(mask, toks[:, t], 0).astype(np.int32)


def test_service_real_model_ragged_roundtrip():
    """End-to-end with a jitted model: ragged jobs through the service,
    cross-decoded against the grouped compressor, plus decode at a slot
    count different from the encoder's batch shape."""
    pred = _model_pred("dense")
    svc = CompressionService(pred, slots=4, chunk_size=16, topk=8)
    comp = LLMCompressor(pred, chunk_size=16, topk=8, decode_batch=4,
                         container_version=4)
    rng = np.random.default_rng(3)
    datas = [rng.integers(0, 256, n).astype(np.int32)
             for n in (5, 33, 90, 64)]
    handles = [svc.submit_compress(d) for d in datas]
    blobs = [h.result()[0] for h in handles]
    for d, b in zip(datas, blobs):
        assert np.array_equal(comp.decompress(b), d)
        assert np.array_equal(svc.submit_decompress(b).result(), d)
    # different fixed shape than the 4-lane encoder program
    svc6 = CompressionService(pred, slots=6, chunk_size=16, topk=8)
    assert np.array_equal(svc6.submit_decompress(blobs[2]).result(),
                          datas[2])


# ------------------------------------------------------------ error paths
def test_submit_rejects_mismatched_container():
    toks = golden_tokens(40)
    blob, _ = _golden_compressor(chunk=16).compress(toks)
    svc = _golden_service(chunk=32)          # wrong chunk size
    with pytest.raises(ContainerError):
        svc.submit_decompress(blob)


def test_short_stream_rejected_at_submit():
    """A corrupt length varint can yield a stream shorter than the rANS
    state flush; that must fail at submit with ContainerError — not
    mid-step with a bare ValueError and a stranded slot."""
    from repro.core.compressor import CODEC_RANS, write_container
    svc = _golden_service(slots=2, chunk=16)
    blob = write_container([b"xx"], version=3, chunk_size=16, n_tokens=5,
                           vocab=svc.predictor.vocab_size, topk=8,
                           precision=svc.precision, codec_id=CODEC_RANS)
    with pytest.raises(ContainerError, match="cannot code"):
        svc.submit_decompress(blob)


def test_corrupt_v3_stream_fails_loudly():
    """v3 has no checksums, but a bit-flipped rANS stream leaves the coder
    state dirty at end-of-chunk — the scheduler's exhaustion check turns
    that into ContainerError instead of silently wrong tokens."""
    toks = golden_tokens(64)
    blob, _ = _golden_compressor().compress(toks)     # v3, rans
    bad = bytearray(blob)
    bad[len(bad) // 2] ^= 0x10                        # flip inside a stream
    svc = _golden_service()
    got_error = False
    try:
        out = svc.submit_decompress(bytes(bad)).result()
        got_error = not np.array_equal(out, toks)     # wrong-token detect
    except ContainerError:
        got_error = True
    assert got_error
