"""The seed-and-extend mapping pipeline: index, seeding, chaining,
banded extension, the ReadMapper facade, and the serve channel."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import alphabets
from repro.data.synthetic import junk_reads, sample_reads
from repro.mapping import (FLAG_REVERSE, ReadMapper, build_index,
                           chain_anchors, cigar_spans, kmer_hashes,
                           minimizers, seed_anchors, top_anchors)
from repro.mapping import index as index_mod
from repro.obs import trace as obs_trace
from repro.runtime import plan as plan_mod


# ---------------------------------------------------------------------------
# index
# ---------------------------------------------------------------------------
def test_kmer_hashes_deterministic_and_position_free(rng):
    seq = alphabets.random_dna(rng, 120)
    h1 = np.asarray(kmer_hashes(jnp.asarray(seq), 13))
    h2 = np.asarray(kmer_hashes(jnp.asarray(seq), 13))
    np.testing.assert_array_equal(h1, h2)
    # the same k-mer hashes identically wherever it occurs
    dup = np.concatenate([seq[:40], seq[:40]])
    hd = np.asarray(kmer_hashes(jnp.asarray(dup), 13))
    np.testing.assert_array_equal(hd[:20], hd[40:60])


def test_minimizers_are_window_minima(rng):
    k, w = 13, 8
    seq = alphabets.random_dna(rng, 200)
    h = np.asarray(kmer_hashes(jnp.asarray(seq), k))
    pos, val = minimizers(jnp.asarray(seq), k, w)
    pos, val = np.asarray(pos), np.asarray(val)
    assert pos.shape == (len(seq) - k - w + 2,)
    for t in range(len(pos)):
        window = h[t: t + w]
        assert val[t] == window.min()
        assert t <= pos[t] < t + w
        assert h[pos[t]] == val[t]


def test_sketch_matches_numpy_packing_and_leftmost_minimum(rng):
    k, w = 5, 4                   # short k: repeated hashes in windows
    seq = alphabets.random_dna(rng, 400)
    seq[100:103] = 4
    n = len(seq) - k + 1
    codes = np.stack([seq[t:t + n] for t in range(k)], axis=1).astype(np.int64)
    packed = ((codes & 3) << (2 * np.arange(k - 1, -1, -1))).sum(axis=1)
    want_h = np.where((codes < 4).all(axis=1),
                      np.asarray(index_mod.mix32(packed.astype(np.uint32))),
                      index_mod.AMBIG_HASH)
    h = np.asarray(kmer_hashes(jnp.asarray(seq), k))
    np.testing.assert_array_equal(h, want_h)
    pos, val = minimizers(jnp.asarray(seq), k, w)
    hw = np.stack([h[t:t + n - w + 1] for t in range(w)], axis=1)
    np.testing.assert_array_equal(np.asarray(pos),
                                  np.arange(len(hw)) + hw.argmin(axis=1))
    np.testing.assert_array_equal(np.asarray(val), hw.min(axis=1))


def test_sketch_lowers_without_gather():
    """The index sketch is shifted slices: an (n, k) gather over a 1 Mb
    reference takes the TPU compiler minutes."""
    import jax

    hlo = index_mod._sketch.lower(
        jax.ShapeDtypeStruct((1 << 12,), jnp.uint8), 13, 8).as_text()
    assert "gather" not in hlo


def test_build_index_sorted_table_roundtrip(rng):
    ref = alphabets.random_dna(rng, 2000)
    idx = build_index(ref, k=13, w=8)
    h = np.asarray(idx.hashes)
    p = np.asarray(idx.positions)
    assert np.all(np.diff(h.astype(np.int64)) >= 0)          # sorted
    all_h = np.asarray(kmer_hashes(jnp.asarray(ref), 13))
    np.testing.assert_array_equal(all_h[p], h)               # true positions
    lo, hi = index_mod.lookup_range(idx, idx.hashes[:50])
    assert np.all(np.asarray(lo) < np.asarray(hi))


# ---------------------------------------------------------------------------
# seeding
# ---------------------------------------------------------------------------
def test_seed_anchors_exact_read_all_on_one_diagonal(rng):
    ref = alphabets.random_dna(rng, 4000)
    idx = build_index(ref, k=13, w=8)
    p = 1234
    read = ref[p: p + 100]
    q, r, v = seed_anchors(idx, jnp.asarray(read), 100)
    q, r, v = np.asarray(q), np.asarray(r), np.asarray(v)
    assert v.sum() >= 3
    np.testing.assert_array_equal(r[v] - q[v], p)


def test_ambiguous_bases_are_masked_not_packed(rng):
    """N (code 4) k-mers hash to the dropped sentinel instead of
    corrupting neighboring bases' bits; reads still map around them."""
    ref = alphabets.random_dna(rng, 4000)
    ref_n = ref.copy()
    ref_n[1000:1010] = 4                        # an N run
    idx = build_index(ref_n, k=13, w=8)
    assert np.all(np.asarray(idx.hashes) != index_mod.AMBIG_HASH)
    h = np.asarray(kmer_hashes(jnp.asarray(ref_n), 13))
    covers_n = (np.arange(len(h)) + 13 > 1000) & (np.arange(len(h)) <= 1009)
    assert np.all(h[covers_n] == index_mod.AMBIG_HASH)
    assert np.all(h[~covers_n] != index_mod.AMBIG_HASH)
    mapper = ReadMapper(ref_n)
    (rec,) = mapper.map_reads([ref_n[2000:2150]])
    assert rec.is_mapped and rec.pos - 1 == 2000


def test_map_reads_accepts_jnp_and_list_inputs_with_lens(rng):
    ref = alphabets.random_dna(rng, 4096)
    rs = sample_reads(ref, 4, 120, error_rate=0.05, seed=9)
    mapper = ReadMapper(ref)
    base = mapper.map_reads(rs.reads, rs.lens)
    via_jnp = mapper.map_reads(jnp.asarray(rs.reads), rs.lens)
    via_list = mapper.map_reads(list(rs.reads), rs.lens)
    for a, b, c in zip(base, via_jnp, via_list):
        assert (a.pos, a.cigar, a.flag) == (b.pos, b.cigar, b.flag)
        assert (a.pos, a.cigar, a.flag) == (c.pos, c.cigar, c.flag)


def test_seed_anchors_masks_padding(rng):
    ref = alphabets.random_dna(rng, 4000)
    idx = build_index(ref, k=13, w=8)
    read = np.zeros((128,), np.uint8)
    read[:64] = ref[500:564]
    q, _, v = seed_anchors(idx, jnp.asarray(read), 64)
    q, v = np.asarray(q), np.asarray(v)
    assert np.all(q[v] <= 64 - 13)        # no anchors from the padded tail


def test_top_anchors_exact_order_beyond_2mb():
    """Anchor sort keys must keep exact (r_pos, q_pos) order over the full
    int32 coordinate range (regression: the packed int32 key
    ``r_pos * 1024 + q_pos`` wrapped negative past ~2 Mb references,
    silently corrupting anchor order — wrong mappings, no error)."""
    r = jnp.asarray([3_000_000, 10, 2_500_000, 3_000_000, 7], jnp.int32)
    q = jnp.asarray([5, 3, 7, 2, 9], jnp.int32)
    v = jnp.asarray([True, True, True, True, False])
    qo, ro, vo = top_anchors(q, r, v, 5)
    assert np.asarray(ro)[:4].tolist() == [10, 2_500_000,
                                           3_000_000, 3_000_000]
    assert np.asarray(qo)[:4].tolist() == [3, 7, 2, 5]   # q_pos tie-break
    assert np.asarray(vo).tolist() == [True, True, True, True, False]


def test_mapper_places_reads_on_reference_beyond_2mb(rng):
    """End-to-end guard: reads drawn from past the 2 Mb mark of a large
    reference must map back to their true origin."""
    tail = alphabets.random_dna(rng, 4096)
    ref = np.concatenate([np.zeros(2_200_000, np.uint8), tail])
    origin = 2_200_000 + 1000
    read = ref[origin: origin + 150]
    mapper = ReadMapper(ref)
    (rec,) = mapper.map_reads([read])
    assert rec.is_mapped
    assert abs((rec.pos - 1) - origin) <= 5


# ---------------------------------------------------------------------------
# chaining
# ---------------------------------------------------------------------------
def _sorted_anchors(q, r, valid, n_anchors=64):
    out = top_anchors(jnp.asarray(q, jnp.int32), jnp.asarray(r, jnp.int32),
                      jnp.asarray(valid), n_anchors)
    return out


def test_chain_picks_colinear_run_over_noise(rng):
    q = np.arange(10, 80, 10, np.int32)                    # 7 colinear
    r = q + 500
    noise_q = rng.integers(0, 90, 12).astype(np.int32)
    noise_r = rng.integers(2000, 3000, 12).astype(np.int32)
    qq = np.concatenate([q, noise_q])
    rr = np.concatenate([r, noise_r])
    ch = chain_anchors(*_sorted_anchors(qq, rr, np.ones(len(qq), bool)),
                       13, 100)
    assert int(ch.n_anchors) >= 6
    assert int(ch.r_start) - int(ch.q_start) == 500
    assert int(ch.d_min) == int(ch.d_max) == 500
    assert float(ch.score) > float(ch.score2)


def test_chain_tracks_diagonal_drift():
    q = np.asarray([10, 30, 50, 70], np.int32)
    r = np.asarray([110, 132, 151, 173], np.int32)         # diag 100..103
    ch = chain_anchors(*_sorted_anchors(q, r, np.ones(4, bool)), 13, 100)
    assert int(ch.n_anchors) == 4
    assert (int(ch.d_min), int(ch.d_max)) == (100, 103)


def test_chain_no_valid_anchors_scores_negative():
    q = np.zeros((8,), np.int32)
    r = np.zeros((8,), np.int32)
    ch = chain_anchors(*_sorted_anchors(q, r, np.zeros(8, bool)), 13, 100)
    assert float(ch.score) < 0


# ---------------------------------------------------------------------------
# extension + end-to-end
# ---------------------------------------------------------------------------
def test_mapper_recovers_exact_indel(rng):
    ref = alphabets.random_dna(rng, 4096)
    mapper = ReadMapper(ref)
    # one deletion: read drops ref base 300+50
    read_del = np.concatenate([ref[300:350], ref[351:450]])
    # one insertion at read offset 60
    read_ins = np.concatenate([ref[700:760], np.asarray([2], np.uint8),
                               ref[760:840]])
    rec_d, rec_i = mapper.map_reads([read_del, read_ins])
    assert rec_d.pos - 1 == 300
    rs, fs = cigar_spans(rec_d.cigar)
    assert (rs, fs) == (len(read_del), len(read_del) + 1)
    assert "D" in rec_d.cigar and "I" not in rec_d.cigar
    assert rec_i.pos - 1 == 700
    rs, fs = cigar_spans(rec_i.cigar)
    assert (rs, fs) == (len(read_ins), len(read_ins) - 1)
    assert "I" in rec_i.cigar and "D" not in rec_i.cigar


def test_mapper_end_to_end_accuracy(rng):
    ref = alphabets.random_dna(rng, 8192)
    rs = sample_reads(ref, 30, 150, error_rate=0.08, seed=3)
    mapper = ReadMapper(ref)
    recs = mapper.map_reads(rs.reads, rs.lens)
    assert len(recs) == 30
    hits = 0
    for i, rec in enumerate(recs):
        if rec.is_mapped and abs((rec.pos - 1) - int(rs.pos[i])) <= 5:
            hits += 1
            assert cigar_spans(rec.cigar)[0] == int(rs.lens[i])
            assert rec.is_reverse == bool(rs.strand[i])
            assert 0 <= rec.mapq <= 60
    assert hits / 30 >= 0.95


def test_mapper_random_read_is_unmapped(rng):
    ref = alphabets.random_dna(rng, 8192)
    mapper = ReadMapper(ref)
    alien = alphabets.random_dna(np.random.default_rng(999), 150)
    (rec,) = mapper.map_reads([alien])
    assert not rec.is_mapped
    assert rec.pos == 0 and rec.mapq == 0 and rec.cigar == ""


def test_junk_reads_carry_one_planted_reference_kmer(rng):
    ref = alphabets.random_dna(rng, 4096)
    kmers = {ref[p:p + 20].tobytes() for p in range(len(ref) - 19)}
    reads = junk_reads(rng, ref, 16, 150)
    assert len(reads) == 16
    for read in reads:
        assert read.dtype == np.uint8 and len(read) == 150
        assert any(read[o:o + 20].tobytes() in kmers for o in range(131))


def test_filter_ladder_rejects_chimeric_junk_without_costing_accuracy(rng):
    """Chimeric junk seeds and chains on its planted 20-mer, then has no
    placement: the myers screen stops every junk read before extension,
    and genuine reads map at least as well as with the screen off."""
    ref = alphabets.random_dna(rng, 8192)
    rs = sample_reads(ref, 20, 150, error_rate=0.05, seed=1)
    reads = [rs.reads[i, :rs.lens[i]] for i in range(20)]
    reads += junk_reads(rng, ref, 20, 150)
    accuracy, extended = {}, {}
    obs_trace.enable()
    try:
        for mode in ("myers", "off"):
            obs_trace.clear()
            recs = ReadMapper(ref, filter_mode=mode).map_reads(reads)
            (span,) = [s for s in obs_trace.spans() if s.name == "map.extend"]
            extended[mode] = span.args["n"]
            accuracy[mode] = sum(
                recs[i].is_mapped and
                abs((recs[i].pos - 1) - int(rs.pos[i])) <= 5
                for i in range(20)) / 20
            assert not any(r.is_mapped for r in recs[20:])
    finally:
        obs_trace.disable()
        obs_trace.clear()
    assert extended == {"myers": 20, "off": 40}
    assert accuracy["myers"] >= max(accuracy["off"], 0.95)


def test_extension_reuses_plan_cache_across_calls(rng):
    ref = alphabets.random_dna(rng, 8192)
    rs = sample_reads(ref, 12, 150, error_rate=0.05, seed=5)
    mapper = ReadMapper(ref)
    plan_mod.clear_plan_cache()
    mapper.map_reads(rs.reads, rs.lens)
    size1 = plan_mod.plan_cache_info()["size"]
    assert size1 >= 1
    rs2 = sample_reads(ref, 12, 150, error_rate=0.05, seed=6)
    mapper.map_reads(rs2.reads, rs2.lens)
    info = plan_mod.plan_cache_info()
    assert info["size"] == size1          # nothing new compiled
    assert info["hits"] > 0


def test_sam_output_well_formed(rng):
    ref = alphabets.random_dna(rng, 4096)
    rs = sample_reads(ref, 4, 120, error_rate=0.05, seed=7)
    mapper = ReadMapper(ref, rname="chr_test")
    recs = mapper.map_reads(rs.reads, rs.lens)
    sam = mapper.to_sam(recs)
    lines = sam.strip().split("\n")
    assert lines[0].startswith("@HD")
    assert any(ln.startswith("@SQ\tSN:chr_test\tLN:4096") for ln in lines)
    body = [ln for ln in lines if not ln.startswith("@")]
    assert len(body) == 4
    for ln in body:
        fields = ln.split("\t")
        assert len(fields) >= 11
        assert fields[2] == "chr_test"
        assert len(fields[9]) >= 100      # SEQ column carries the read


# ---------------------------------------------------------------------------
# serve channel
# ---------------------------------------------------------------------------
def test_read_mapping_service_channel(rng):
    from repro.serve import MapRequest, ReadMappingService
    ref = alphabets.random_dna(rng, 8192)
    rs = sample_reads(ref, 10, 150, error_rate=0.05, seed=11)
    svc = ReadMappingService(ref, block=4)
    reqs = [MapRequest(rid=i, read=rs.reads[i, : rs.lens[i]])
            for i in range(10)]
    for r in reqs:
        svc.submit(r)
    assert svc.drain() == 10
    # the whole queue goes to the mapper in one call (the extension stage
    # pipelines best over the full job list), block=4 only sizes the
    # mapper's internal batches
    assert list(svc.dispatches) == [{"n": 10}]
    for i, req in enumerate(reqs):
        assert req.result is not None
        assert req.result["mapped"]
        assert abs((req.result["pos"] - 1) - int(rs.pos[i])) <= 5
        assert req.result["sam"].startswith(f"r{i}\t")


def test_read_mapping_service_max_batch_chunks(rng):
    from repro.serve import MapRequest, ReadMappingService
    ref = alphabets.random_dna(rng, 8192)
    rs = sample_reads(ref, 10, 150, error_rate=0.05, seed=11)
    svc = ReadMappingService(ref, block=4, max_batch=4)
    for i in range(10):
        svc.submit(MapRequest(rid=i, read=rs.reads[i, : rs.lens[i]]))
    assert svc.drain() == 10
    assert [d["n"] for d in svc.dispatches] == [4, 4, 2]


def test_read_mapping_service_requeues_on_failure(rng, monkeypatch):
    """A raising map_reads must not lose the popped requests."""
    import pytest
    from repro.serve import MapRequest, ReadMappingService
    ref = alphabets.random_dna(rng, 8192)
    rs = sample_reads(ref, 6, 150, error_rate=0.05, seed=11)
    svc = ReadMappingService(ref, block=4)
    reqs = [MapRequest(rid=i, read=rs.reads[i, : rs.lens[i]])
            for i in range(6)]
    for r in reqs:
        svc.submit(r)
    real = svc.mapper.map_reads
    boom = {"armed": True}

    def exploding(reads, lens=None, names=None):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected mapper failure")
        return real(reads, lens, names)

    monkeypatch.setattr(svc.mapper, "map_reads", exploding)
    with pytest.raises(RuntimeError, match="injected"):
        svc.drain()
    assert svc.queue == reqs                  # nothing lost, order kept
    assert svc.drain() == 6
    assert all(r.result is not None for r in reqs)
