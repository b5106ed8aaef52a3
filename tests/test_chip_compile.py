"""Ask the chip's compiler, without the chip (round 22).

The tests pin JAX to the CPU, where no Mosaic kernel ever runs — the
XLA twins are that platform's path. This file compiles the main path's
Mosaic kernels, and the jitted programs the engines really dispatch
with the Pallas branch forced, for a *described* ``v5e:2x2`` topology
(one device, shapes only): what the TPU compiler refuses here — a
misaligned slice, too much VMEM, a program over the chip's 16 GB — it
would refuse on the chip, where since round 22 a refused kernel fails
the run instead of quietly falling back to XLA. Nothing executes, so
this says nothing about values or times; ``chip_smoke.py`` does that
on the chip.

Everything chip-shaped lives in fixtures of THIS file: only one
process may load the TPU library, pytest-xdist gives a file to one
worker, and describing the topology at import would break every other
worker's collection. The persistent compile cache is off around the
compiles (a described-chip entry cannot be read back without a chip).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from racon_tpu.ops import nw, pallas_nw, poa, swar

GIB = 1 << 30
# what the v5e compiler reports as usable of the chip's 16 GB
HBM_BYTES = int(15.75 * GIB)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` -> a ShapeDtypeStruct placed on one
    described chip, with the persistent compile cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(lowered):
    """Compile for the described chip; returns (compiled, total bytes
    the program needs on the device)."""
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    return compiled, ma, total


def _rows(chip, B, max_len, band):
    width = band // 2 + max_len + band
    return chip((B, width), jnp.uint8), chip((B,), jnp.int32)


# ------------------------------------------------------ aligner kernels

# every bucket at its own band, plus ladder rungs under the long
# buckets (the 16384 x 8192 escape bucket and the 4096-band rung of
# 768-lane consensus take 10-25 s each and are left to the chip)
KERNEL_GEOMETRIES = [(256, 128), (1024, 384), (4096, 1024), (8192, 2048),
                     (16384, 4096), (4096, 256), (8192, 768),
                     (16384, 1536)]


def test_kernel_geometries_are_the_engines():
    """The list above tracks the engine's tables: buckets verbatim,
    rungs from ``BAND_RUNGS`` and under their bucket's band."""
    bands = dict((m, b) for m, b in nw.BUCKETS[:5])
    for max_len, band in KERNEL_GEOMETRIES:
        assert (max_len, band) in nw.BUCKETS or (
            band in nw.BAND_RUNGS and band < bands[max_len])


@pytest.mark.parametrize("max_len,band", KERNEL_GEOMETRIES)
@pytest.mark.parametrize("use_swar", [False, True])
def test_aligner_kernels_compile(chip, max_len, band, use_swar):
    """``pallas_nw_fwd`` (int32 and SWAR) and ``pallas_walk_ops`` at
    one 64-pair block of each geometry."""
    B = 64
    steps = nw._sweep_bound(2 * max_len, max_len)
    rows, lens = _rows(chip, B, max_len, band)
    assert not use_swar or swar.swar_fits(max_len)
    compiled, _, _ = _compile(pallas_nw.pallas_nw_fwd.lower(
        rows, rows, lens, lens, max_len=max_len, band=band, steps=steps,
        out_quant=512, use_swar=use_swar))
    assert "tpu_custom_call" in compiled.as_text()
    if not use_swar:  # the walk is shared by both forward variants
        dirs = chip((B, steps, band // 8), jnp.uint8)
        compiled, _, _ = _compile(pallas_nw.pallas_walk_ops.lower(
            dirs, lens, lens, band=band))
        assert "tpu_custom_call" in compiled.as_text()


def test_probe_shapes_compile(chip):
    """The B=8 programs ``pallas_ok()`` / ``pallas_swar_ok()`` run
    first thing on the chip (a refusal there stops every run)."""
    max_len, band, B = swar.PROBE_MAX_LEN, swar.PROBE_BAND, 8
    rows, lens = _rows(chip, B, max_len, band)
    for use_swar in (False, True):
        _compile(pallas_nw.pallas_nw_fwd.lower(
            rows, rows, lens, lens, max_len=max_len, band=band,
            out_quant=512, use_swar=use_swar))
    dirs = chip((B, 2 * max_len, band // 8), jnp.uint8)
    _compile(pallas_nw.pallas_walk_ops.lower(dirs, lens, lens, band=band))
    _compile(pallas_nw.pallas_walk_vote.lower(
        dirs, lens, lens, lens, chip((B, max_len), jnp.uint16),
        band=band, L=max_len, K=4, CH=poa.CH, DEL=poa.DEL))


# ------------------------------------- the programs the engines dispatch

# (max_len, band, steps) of the 2 Mbp smoke workload's big align chunks
# (30x reads of 2-8 kb against a ~10% draft: the stream's chunk plan,
# from the lengths in its PAF); each at the engine's OWN pair cap for
# that geometry, i.e. the largest chunk it would ever dispatch
ALIGN_CHUNKS = [(16384, 4096, 16384), (16384, 3072, 14336),
                (8192, 2048, 8192),
                # round 2 of a --rounds job (bact1m-auto30x-r2: the same
                # reads on a polished draft class differently along the
                # ladder; PR 41's warm-up job compiled these two anew)
                (16384, 3072, 16384), (8192, 768, 4096)]


@pytest.mark.parametrize("max_len,band,steps", ALIGN_CHUNKS)
def test_aligner_chunk_program_fits_the_chip(chip, max_len, band, steps):
    """The fused Mosaic sweep+walk program at a budget-sized chunk:
    compiles, fits the chip, and holds the direction matrix ONCE — as
    two programs each side kept a relayout copy of it, which the
    compiler refuses for an 8 GiB chunk."""
    eng = nw.TpuAligner(fallback=None, num_batches=1)
    B = min(eng._chunk_cap(steps, band), 1024)
    dirs_bytes = B * steps * (band // 8)
    assert dirs_bytes <= eng.chunk_dirs_budget()
    rows, lens = _rows(chip, B, max_len, band)
    compiled, ma, total = _compile(nw._pallas_align_chain.lower(
        rows, rows, lens, lens, max_len=max_len, band=band, steps=steps,
        use_swar=True))
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert total < HBM_BYTES, f"{total / GIB:.2f} GiB"
    assert ma.temp_size_in_bytes < 1.1 * dirs_bytes, (
        f"temp {ma.temp_size_in_bytes / GIB:.2f} GiB for a "
        f"{dirs_bytes / GIB:.2f} GiB direction matrix: a relayout copy "
        f"is back")
    # the chunk's neighbours on the device: the row builder before it
    # and the breaking-point reduction after it
    blk = chip((B * max_len // 4,), jnp.uint8)
    _compile(nw._build_rows_packed2.lower(blk, blk, lens, lens,
                                          max_len=max_len, band=band))
    if max_len == 8192:  # one is enough: plain XLA, ~7 s at B=1024
        _compile(nw._breaking_points_kernel.lower(
            chip((B, steps // 4), jnp.uint8), lens, lens, lens, lens,
            w=500, NW=max_len // 500 + 2))


# the short-read cell (bact-sr150-50x: 345 k pairs of 100-150 bases)
# runs the smallest bucket only, at its three bands: the probe's and
# the cold seeds' at the bucket band, the ladder's two rungs after it;
# every chunk at the pair cap (``MAX_CHUNK_PAIRS``), not the byte budget
SHORT_READ_BANDS = [128, 96, 64]


@pytest.mark.parametrize("band", SHORT_READ_BANDS)
def test_short_read_chunk_program_fits_the_chip(chip, band):
    """``_pallas_align_chain`` at ``(256, band)`` with 65,536 pairs a
    chunk, and its two neighbours: a refused kernel here would fail the
    cell's every run, since nothing falls back to XLA on the chip. The
    int32 forward kernel: bands under ``swar.MOSAIC_SWAR_MIN_BAND``
    never take the packed one (wrong there on the chip, PR 37)."""
    max_len, bucket_band = nw.BUCKETS[0]
    assert (max_len, bucket_band) == (256, 128)
    assert band == bucket_band or band in nw.BAND_RUNGS
    eng = nw.TpuAligner(fallback=None, num_batches=1)
    steps = nw._sweep_bound(2 * 150, max_len)
    B = eng._chunk_cap(steps, band)
    assert (steps, B) == (512, nw.MAX_CHUNK_PAIRS)
    assert not swar.mosaic_swar_fits(band)
    rows, lens = _rows(chip, B, max_len, band)
    compiled, _, total = _compile(nw._pallas_align_chain.lower(
        rows, rows, lens, lens, max_len=max_len, band=band, steps=steps,
        use_swar=False))
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert total < HBM_BYTES // 8, f"{total / GIB:.2f} GiB"
    blk = chip((B * max_len // 4,), jnp.uint8)
    _compile(nw._build_rows_packed2.lower(blk, blk, lens, lens,
                                          max_len=max_len, band=band))
    _compile(nw._breaking_points_kernel.lower(
        chip((B, steps // 4), jnp.uint8), lens, lens, lens, lens,
        w=500, NW=max_len // 500 + 2))


def _consensus_engine(band=poa.BAND):
    return poa.TpuPoaConsensus(3, -5, -4, fallback=None, band=band)


def _refine_args(chip, Lq, Lb, B, nWp):
    i32, u8, f32 = jnp.int32, jnp.uint8, jnp.float32
    static = (chip((B,), i32), chip((B, Lq), jnp.uint16),
              chip((B,), i32), chip((B,), bool))
    state = (chip((B,), i32), chip((B,), i32), chip((nWp, Lb), u8),
             chip((nWp, Lb), f32), chip((nWp,), i32),
             chip((nWp, Lb), i32), chip((nWp,), bool),
             chip((nWp,), bool), chip((nWp,), bool),
             chip((1, 4 + nWp), i32))
    return static + state + (chip((), f32), chip((), f32))


def test_consensus_group_program_fits_the_chip(chip):
    """``_refine_loop_packed`` — the consensus engine's one program per
    group — with ``use_pallas=True`` at the full-arena group a 2 Mbp,
    30x run dispatches (the engine's own warm-up shape rule)."""
    eng = _consensus_engine()
    Lq, Lb, band, steps, Lq2, B, nWp, rounds = eng._warmup_shapes(
        500, 120_000, 4_000, 564, 1)[0]
    assert (Lq, Lb, band, B) == (1024, 768, 512, poa.MAX_GROUP_PAIRS)
    compiled, _, total = _compile(poa._refine_loop_packed.lower(
        *_refine_args(chip, Lq, Lb, B, nWp), rounds=rounds,
        n_windows=nWp, max_len=Lq, band=band, Lb=Lb, K=poa.K_INS,
        steps=steps, use_pallas=True, use_swar=True, Lq2=Lq2,
        scores=eng.scores, matmul_votes=eng.use_matmul_votes))
    assert "tpu_custom_call" in compiled.as_text()
    # the group's packed inputs wait in flight beside the running
    # program (MAX_INFLIGHT_BYTES of them at most)
    assert total + poa.MAX_INFLIGHT_BYTES < HBM_BYTES, \
        f"{total / GIB:.2f} GiB"


def test_shard_consensus_group_program_fits_the_chip(chip):
    """What a shard of ``frag2m-shards4-paf30x`` adds: 0.5 Mbp at 30x is
    about 31,150 pairs over 1,000 windows, under the arena's 32,768, so
    a shard's windows close as ONE group at ``finish`` — which takes
    the two-stage schedule by its size like any other (PR 44; until
    then it ran the full round budget in one stage: 24 full-size
    group-rounds a job where the one-shot job of the same 2 Mbp runs
    12). Its stage A is 32,768 rows over 1,024 window rows where the
    one-shot job's groups pad to 2,048; the repack of the 220-255
    windows it leaves is the repack test's (6,500, 240) below. The
    first shard's warm-up derives the stage-A shape from the same
    rule; the groups run it at the sweep their layers need (1,152
    steps, not the estimate's 1,280)."""
    eng = _consensus_engine()
    Lq, Lb, band, steps, Lq2, B, nWp, rounds = eng._warmup_shapes(
        500, 2_142 * 15, 1_002, 564, 2)[0]
    assert (Lq, Lb, band, steps, Lq2, B, nWp, rounds) == (
        1024, 768, 512, 1280, 640, poa.MAX_GROUP_PAIRS, 1024,
        poa.STAGE_A_ROUNDS)
    assert rounds == eng.first_stage_rounds(B) < eng.rounds
    compiled, _, total = _compile(poa._refine_loop_packed.lower(
        *_refine_args(chip, Lq, Lb, B, nWp), rounds=rounds,
        n_windows=nWp, max_len=Lq, band=band, Lb=Lb, K=poa.K_INS,
        steps=1152, use_pallas=True, use_swar=True, Lq2=Lq2,
        scores=eng.scores, matmul_votes=eng.use_matmul_votes))
    assert "tpu_custom_call" in compiled.as_text()
    assert total + poa.MAX_INFLIGHT_BYTES < HBM_BYTES, \
        f"{total / GIB:.2f} GiB"


def test_short_read_consensus_group_program_fits_the_chip(chip):
    """The group the short-read cell forms: 32,768 rows of at most 150
    bases in ``Lq`` 1,024, 200 to a window, so 256 window rows where
    the long-read cells have 2,048, a 384-step sweep and a 256-lane
    vote. The warm-up derives it from the longest overlap."""
    eng = _consensus_engine()
    Lq, Lb, band, steps, Lq2, B, nWp, rounds = eng._warmup_shapes(
        500, 345_000, 2_001, 150, 1)[0]
    assert (Lq, Lb, band, steps, Lq2, B, nWp) == (
        1024, 768, 512, 384, 256, poa.MAX_GROUP_PAIRS, 256)
    compiled, _, total = _compile(poa._refine_loop_packed.lower(
        *_refine_args(chip, Lq, Lb, B, nWp), rounds=rounds,
        n_windows=nWp, max_len=Lq, band=band, Lb=Lb, K=poa.K_INS,
        steps=steps, use_pallas=True, use_swar=True, Lq2=Lq2,
        scores=eng.scores, matmul_votes=eng.use_matmul_votes))
    assert "tpu_custom_call" in compiled.as_text()
    assert total + poa.MAX_INFLIGHT_BYTES < HBM_BYTES, \
        f"{total / GIB:.2f} GiB"


@pytest.mark.parametrize("band", [poa.BAND, poa.BAND // 2])
def test_consensus_vote_kernel_compiles(chip, band):
    """The fused walk+vote kernel at the default band and ``-b``'s."""
    eng = _consensus_engine(band)
    band_, L, Lq, Lb = eng._bucket_geometry(500)
    steps, Lq2 = eng._sweep_geometry(Lq, 564 + 628, 564)
    B = 256
    lens = chip((B,), jnp.int32)
    compiled, _, _ = _compile(pallas_nw.pallas_walk_vote.lower(
        chip((B, steps, band_ // 8), jnp.uint8), lens, lens, lens,
        chip((B, Lq2), jnp.uint16), band=band_, L=Lb, K=poa.K_INS,
        CH=poa.CH, DEL=poa.DEL))
    assert "tpu_custom_call" in compiled.as_text()
    rows, _ = _rows(chip, B, Lq, band_)
    _compile(pallas_nw.pallas_nw_fwd.lower(
        rows, rows, lens, lens, max_len=Lq, band=band_, steps=steps,
        use_swar=True))


# ------------------------------------------------------ the overlapper

def test_overlapper_minimizer_program_compiles(chip):
    """The one minimizer arena (PR 34: every input seeds through this
    single geometry)."""
    from racon_tpu.ops import overlap_seed as seed
    B, L = seed.SEED_BATCH, seed.SEED_ROW
    _, _, total = _compile(seed._minimizer_kernel.lower(
        chip((B, L), jnp.uint8), chip((B,), jnp.int32),
        chip((B,), jnp.int32), k=15, w=5, L=L))
    assert total < HBM_BYTES // 8


def _compile_join(chip, R2, T2, E, Q2):
    """Both programs of the device join at padded tables ``R2`` (the
    read side's kept entries) and ``T2``, ``E`` hit slots and ``Q2``
    reads; returns the ramp's text."""
    from racon_tpu.ops import chain
    u32, i32 = jnp.uint32, jnp.int32
    ramp, _, total = _compile(chain._join_ramp_kernel.lower(
        chip((R2,), u32), chip((T2,), u32), chip((T2,), i32),
        chip((2 * T2 + 1,), i32), chip((), i32),
        steps=chain.JOIN_BUCKET_STEPS))
    assert total < HBM_BYTES // 2
    _, _, total = _compile(chain._join_expand_kernel.lower(
        chip((R2,), i32), chip((R2,), i32), chip((R2,), i32),
        chip((T2,), i32), chip((T2,), i32), chip((T2,), i32),
        chip((T2,), i32),
        chip((R2,), i32), chip((R2,), i32), chip((R2,), i32),
        chip((), i32), chip((Q2,), i32), chip((Q2,), i32), E=E, k=15))
    assert total < HBM_BYTES // 2
    return ramp.as_text()


# read entries that cross to the device, hits: of a 30x 2 Mbp read set's
# 19.3 M minimizers the host's prefilter keeps 8 % (2^21; 14 % and 2^22
# in PR 42's first chip run, whose presence table read the hashes' top
# bits), or all of them where it stands aside
JOIN_READ_SIDES = [(1_600_000, 1_210_178), (2_630_034, 1_210_178),
                   (19_303_946, 1_210_178)]


@pytest.mark.parametrize("kept, hits", JOIN_READ_SIDES)
def test_overlapper_join_programs_fit_the_chip(chip, kept, hits):
    """The device join at the tables of a 30x 2 Mbp read set: the
    draft's 0.67 M minimizers pad to 2^20 under a directory of 2^21
    buckets, 1.2 M hits to 2^21, and of the read minimizers the ones
    that can match pad to 2^21 or 2^22 (PR 42) — or the whole table to
    2^25, the class a prefilter that stands aside uploads. The
    look-up, the bucket counts and the ramp in one program, then the
    expansion. Neither holds a sort (PR 34: the sorts they replaced
    took this compiler 70, 44 and 198 s)."""
    from racon_tpu.ops import chain
    R2, T2 = chain._table_pad(kept), chain._table_pad(670_000)
    assert R2 in (1 << 21, 1 << 22, 1 << 25) and T2 == 1 << 20
    assert R2 + T2 <= chain.JOIN_TABLE_CELLS
    ramp = _compile_join(chip, R2, T2, chain._hits_pad(hits),
                         chain._table_pad(8571))
    assert "sort" not in ramp.lower()


@pytest.mark.parametrize("kept, hits", [(790_000, 600_000),
                                        (2_000_000, 1_500_000),
                                        (2_200_000, 1_500_000),
                                        (9_650_000, 1_500_000)])
def test_second_round_join_program_fits_the_chip(chip, kept, hits):
    """The joins of ``bact1m-auto30x-r2`` (1.0 Mbp at 30x: 9.65 M read
    minimizers, the draft's 0.33 M in 2^19): round 1 keeps one read
    entry in twelve (2^20) for 0.6 M hits in 2^20; against a polished
    draft a fifth of a read's minimizers match, so round 2 keeps 2.0 to
    2.2 M — on either side of 2^21 — its hits pad to 2^21 and its pairs
    reach the 1,024-seed chain class
    (``test_overlapper_chain_program_compiles``; PR 41: the warm-up
    job's round 2 compiled 21 programs of its own). Last the table as
    it crossed before the prefilter: 2^24."""
    from racon_tpu.ops import chain
    R2, T2 = chain._table_pad(kept), chain._table_pad(334_000)
    E, Q2 = chain._hits_pad(hits), chain._table_pad(4285)
    assert (T2, Q2) == (1 << 19, 8192)
    assert (R2, E) in ((1 << 20, 1 << 20), (1 << 21, 1 << 21),
                       (1 << 22, 1 << 21), (1 << 24, 1 << 21))
    _compile_join(chip, R2, T2, E, Q2)


@pytest.mark.parametrize("pairs, windows, stage_a, shape", [
    (13_000, 470, (32768, 2048), (16384, 512)),
    (2_000, 65, (32768, 2048), (4096, 256)),
    (6_500, 240, (32768, 1024), (8192, 256))])
def test_repack_programs_of_the_rounds_fit_the_chip(chip, pairs, windows,
                                                    stage_a, shape):
    """The consensus engine's second stage in ``bact1m-auto30x-r2``, whose
    stage-A groups are 32,768 rows over 2,048 windows at the largest.
    Round 1 leaves about 470 windows of 13,000 pairs: 16,384 over 512.
    Round 2, on a polished draft, leaves 60-70 of about 2,000, under the
    repack's floor of an eighth (``poa.STAGE_B_MAX_SHRINK``): 4,096 over
    256 (their own powers of two, 2,048 over 64 or 128, moved with the
    seed, and a run that compiled one read 1.3 GB more host memory).
    Last a shard of ``frag2m-shards4-paf30x`` (PR 44): its lone group of
    32,768 rows over 1,024 leaves 220-255 windows of 6,000-7,000 pairs,
    8,192 over 256."""
    eng = _consensus_engine()
    B = max(eng._pow2_at_least(pairs),
            stage_a[0] // poa.STAGE_B_MAX_SHRINK)
    nWp = max(eng._pow2_at_least(windows + 1),
              stage_a[1] // poa.STAGE_B_MAX_SHRINK)
    assert (B, nWp) == shape
    Lq, Lb, band, steps, Lq2, rounds = 1024, 768, 512, 1152, 640, 4
    compiled, _, total = _compile(poa._refine_loop_packed.lower(
        *_refine_args(chip, Lq, Lb, B, nWp), rounds=rounds,
        n_windows=nWp, max_len=Lq, band=band, Lb=Lb, K=poa.K_INS,
        steps=steps, use_pallas=True, use_swar=True, Lq2=Lq2,
        scores=eng.scores, matmul_votes=eng.use_matmul_votes))
    assert "tpu_custom_call" in compiled.as_text()
    assert total < HBM_BYTES // 8, f"{total / GIB:.2f} GiB"


@pytest.mark.parametrize("S", [16, 64, 256, 1024])
def test_overlapper_chain_program_compiles(chip, S):
    from racon_tpu.ops import chain
    B = chain._pair_batch(S)
    _, _, total = _compile(chain._chain_kernel.lower(
        chip((B, S), jnp.int32), chip((B, S), jnp.int32),
        chip((B,), jnp.int32), S=S, k=15))
    assert total < HBM_BYTES // 8
