"""The main path's programs, put to the TPU v5e's own compiler at 1M x 300
and at the benchmark's sizes (2M x 300 on one chip, 10M x 300 over four).

Nothing here runs: the chip is *described* (``v5e:2x2``), programs are
lowered from ``ShapeDtypeStruct``s and compiled by the installed TPU
compiler, which raises what the chip's compiler would raise — a kernel it
refuses, a program that does not fit 16 GB. A compile that passes is not a
chip run and is never reported as one; ``python chip_smoke.py`` is the run.

* The XLA programs ``chip_smoke.py`` drives (the packed corpus scan with
  per-pair and shared-pool negatives, ``subsample_compact``, the query
  family serving warm-up compiles) must compile and fit, on one chip and,
  for the sharded path, on the 1x4 mesh of ``chip_smoke.py --chips 4``.
* The tables arrive as the engine keeps them (``padded_vocab`` rows of
  ``padded_dim`` columns, whole lanes, in the engine's own sharding and the
  device's default layout), so what compiles here is what the engine runs;
  at the benchmark's sizes that default must be row-major, in and out, and
  no program may copy a whole table (PR 28).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU's library, and every xdist worker imports
every test file. Keep these tests in this one file for the same reason.
"""

import numpy as np
import pytest

V, D, NEG = 1_000_000, 300, 5
# chip_smoke.py's training geometry (bench.py's headline shape).
BATCH, WINDOW, STEPS_PER_CALL = 8192, 5, 32
CORPUS_WORDS, CORPUS_SENTENCES = 4_000_000, 100_000
HBM_BYTES = 16 * 10**9  # one v5e chip
# A resting table's rows are whole lanes: 300 columns rest in 384.
D_REST = 384
# The packed scans compiled here: (chips, shared pool, rows, corpus words,
# corpus sentences). The last three are the benchmark's cells and
# GoogleNews' 3M rows, which only the resting layout lets one chip hold.
_X4_WORDS = 15_639_956
SCANS = {
    "1chip-per_pair": (1, 0, V, CORPUS_WORDS, CORPUS_SENTENCES),
    "1chip-shared_pool": (1, 4096, V, CORPUS_WORDS, CORPUS_SENTENCES),
    "4chips-per_pair": (4, 0, V, CORPUS_WORDS, CORPUS_SENTENCES),
    "2m-1chip": (1, 0, 2_000_000, CORPUS_WORDS, CORPUS_SENTENCES),
    "3m-1chip": (1, 0, 3_000_000, CORPUS_WORDS, CORPUS_SENTENCES),
    # benchmark/configs/w2v-300-10m-x4.json's corpus: every word once, 5M
    # Zipf draws in 40-word sentences, 80,000 planted ones of 8 words.
    "10m-4chips": (4, 0, 10_000_000, _X4_WORDS,
                   -(-(_X4_WORDS - 8 * 80_000) // 40) + 80_000),
}
# The subword scans: the packed scan with a (vocab, 32) group table on the
# device, over benchmark/configs/ft-300-1m-2mb.json's corpus (each word
# once, 4M Zipf draws in 40-word sentences, 80,000 planted ones of 8
# words) and fastText's 2,000,000 bucket rows behind the vocabulary's.
BUCKET, MAX_SUBWORDS = 2_000_000, 32
SUBWORD_SCANS = {  # name: (vocabulary, corpus words)
    "ft-1m-2mb": (1_000_000, 1_000_000 + 4_000_000 + 8 * 80_000),
}


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def engines(topo):
    """Engines over DESCRIBED devices, built once per (chips, pool):
    ``_configure`` derives the geometry and ``_build_jitted_fns`` the
    programs; the placing half of the constructor is skipped because a
    described device holds no array."""
    from jax.sharding import Mesh

    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

    built = {}

    def get(chips: int, shared_negatives: int = 0, vocab: int = V,
            extra_rows: int = 0, architecture: str = "skipgram",
            negatives: int = NEG, position_lanes: int = 0):
        key = (chips, shared_negatives, vocab, extra_rows, architecture,
               negatives, position_lanes)
        if key not in built:
            mesh = Mesh(
                np.asarray(topo.devices[:chips]).reshape(1, chips),
                ("data", "model"),
            )
            eng = EmbeddingEngine.__new__(EmbeddingEngine)
            eng._configure(
                mesh, vocab, D, num_negatives=negatives, unigram_power=0.75,
                unigram_table_size=None, seed=1, dtype="float32",
                extra_rows=extra_rows, shared_negatives=shared_negatives,
                compute_dtype=None,
                architecture=architecture, position_lanes=position_lanes,
            )
            eng._build_jitted_fns()
            built[key] = eng
        return built[key]

    return get


def _shapes(eng):
    """``sds(shape, dtype, *spec)``: an abstract array sharded over the
    engine's mesh (replicated with no spec)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(eng.mesh, P(*spec))
        )

    return sds


def _table(eng):
    """An abstract f32 table as the engine keeps one between programs:
    its padded shape and sharding, the layout left to the device."""
    import jax
    import jax.numpy as jnp

    assert eng.padded_dim == D_REST
    return jax.ShapeDtypeStruct(
        (eng.padded_vocab, eng.padded_dim), jnp.float32,
        sharding=eng._table_sharding(),
    )


def _rests(fmt, eng) -> bool:
    """Whether the chip's compiler gave a table argument or result the
    layout the engine counts on for an array it pins nothing on: rows
    contiguous (row-major), in the engine's sharding."""
    return (
        fmt.layout.major_to_minor == (0, 1)
        and fmt.sharding.is_equivalent_to(eng._table_sharding(), 2)
    )


def _whole_table_copies(compiled, eng) -> list:
    """The program's ``copy`` and ``transpose`` ops whose result is a whole
    table shard: a change of layout at the program's edge, or one the
    score pass would make for itself."""
    import re

    n, d = eng.rows_per_shard, eng.padded_dim
    shard = rf"f32\[(?:{n},{d}|{d},{n})\]"
    return [
        line.strip()[:200] for line in compiled.as_text().splitlines()
        if re.search(rf"= {shard}\S* (?:copy|transpose)\(", line)
    ]


def _fits(compiled, chips: int = 1) -> dict:
    """Per-device bytes of one compiled program against the chip's HBM
    (it counts this program alone, not what else the process holds)."""
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    mem = {"total": total, "args": m.argument_size_in_bytes,
           "temp": m.temp_size_in_bytes,
           "aliased": m.alias_size_in_bytes}
    print(f"memory_analysis per device ({chips} chip(s)): {mem}")  # -s shows it
    assert total < HBM_BYTES, mem
    return mem


def _compile_packed_scan(eng, words=CORPUS_WORDS, sentences=CORPUS_SENTENCES,
                         group_width=0):
    """The packed corpus scan at chip_smoke.py's training geometry (26,215
    pairs a step, 5 negatives) over a resident corpus of ``words`` tokens,
    compiled for the engine's described mesh; with ``group_width`` the
    subword family's, a (vocab, group_width) group table its last
    argument. A CBOW engine's scan trains 8,192 positions a step, each
    with its bag, and has no span; with ``position_lanes`` its position
    table is the third argument, beside the tables."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.corpus.batching import (
        context_width,
        packed_pair_batch,
    )

    sds = _shapes(eng)
    P_ = packed_pair_batch(BATCH, WINDOW, 1)
    span = -(-3 * P_ // context_width(WINDOW))
    if eng.architecture == "cbow":
        P_, span = BATCH, 0
    fn = eng._make_packed_corpus_scan(
        P_, WINDOW, BATCH, span, STEPS_PER_CALL, group_width
    )
    vocab = eng.vocab_size
    groups = (sds((vocab, group_width), jnp.int32),) if group_width else ()
    table = _table(eng)
    posw = ((sds((eng.position_lanes, eng.padded_dim), jnp.float32),)
            if eng.position_lanes else ())
    offs = sds((sentences + 1,), jnp.int32)
    i32, u32, f32 = (sds((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    return fn.lower(
        table, table, *posw, sds((-(-vocab // 64), 128), jnp.int32),
        sds((words,), jnp.int32), sds((words,), jnp.int32), offs, offs,
        i32, i32,
        sds((2,), jnp.uint32), u32, u32, f32, f32, f32, *groups,
    ).compile()


@pytest.fixture(scope="module")
def packed_scans(engines):
    """Each packed scan of ``SCANS`` compiled once for the module (the
    chip's compiler takes up to a minute over one): ``get(name)`` gives
    ``(engine, compiled)``."""
    done = {}

    def get(name: str):
        if name not in done:
            chips, shared, vocab, words, sentences = SCANS[name]
            eng = engines(chips, shared, vocab)
            done[name] = eng, _compile_packed_scan(eng, words, sentences)
        return done[name]

    return get


def _table_args_ceiling(vocab: int, chips: int, slack: int) -> float:
    """Bytes a device is handed when the rows really are spread: its share
    of two resting tables and ``slack`` for the replicated rest."""
    return 2 * vocab * D_REST * 4 / chips + slack


@pytest.mark.parametrize(
    "name", ["1chip-per_pair", "1chip-shared_pool", "4chips-per_pair"]
)
def test_packed_corpus_scan_compiles(packed_scans, name):
    eng, compiled = packed_scans(name)
    chips = SCANS[name][0]
    mem = _fits(compiled, chips)
    # The tables are donated: the program must not hold a second pair.
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        2 * eng.rows_per_shard * D * 4
    ), mem
    if chips > 1:
        # Rows really are spread: each device is handed 1/chips of them.
        assert mem["args"] < _table_args_ceiling(V, chips, 64 * 10**6), mem
        assert "all-reduce" in compiled.as_text()
    else:
        # One shard exchanges nothing: the chip's compiler drops the
        # psums over a model axis of one, glint.exchange and all.
        assert "all-reduce" not in compiled.as_text()


def test_packed_corpus_scan_at_the_benchmark_size(packed_scans):
    # The training cell's step (benchmark/configs/w2v-300-2m.json). Until
    # PR 28 it held 7.13 GB of temporaries beside its donated tables: a
    # second pair of tables, in the layout the step wants. With rows of
    # whole lanes the tables rest that way, and what is left is the
    # step's own (1.14 GB).
    _, compiled = packed_scans("2m-1chip")
    mem = _fits(compiled)
    assert mem["temp"] < 1.5e9, mem


# benchmark/configs/w2v-stream-300-2m.json: the streamed fit's tables are the
# 2M-row cell's with 65,536 spare rows behind them, and the view a round's
# buffer: 1,048,576 ids, 131,072 sentences and the pad one.
STREAM_VOCAB, STREAM_SPARE = 2_000_000, 65_536
STREAM_TABLES = 2 * (STREAM_VOCAB + STREAM_SPARE) * D_REST * 4


def test_packed_corpus_scan_at_the_stream_cell_size(engines):
    eng = engines(1, 0, STREAM_VOCAB, extra_rows=STREAM_SPARE)
    mem = _fits(_compile_packed_scan(eng, 1_048_576, 131_073))
    assert STREAM_TABLES == 6_345_326_592  # ISSUE 50's 6.35 GB, 39.7%
    # What the program is handed is the two tables and little else (the
    # buffer, its records, the alias table), it gives them back in place,
    # and the step's own temporaries are the batch cell's.
    assert STREAM_TABLES <= mem["args"] < STREAM_TABLES + 64 * 10**6, mem
    assert mem["aliased"] >= STREAM_TABLES, mem
    assert mem["temp"] < 1.5e9, mem


def test_promotion_program_at_the_stream_cell_size(engines):
    # One program a promotion, whatever the burst: both tables in place.
    import jax.numpy as jnp

    eng = engines(1, 0, STREAM_VOCAB, extra_rows=STREAM_SPARE)
    block, fn = eng._extra_row_writer()
    i32 = _shapes(eng)((), jnp.int32)
    mem = _fits(fn.lower(_table(eng), _table(eng), i32, i32).compile())
    assert block == 256
    assert mem["aliased"] >= STREAM_TABLES, mem
    assert mem["temp"] < 64 * 10**6, mem


def test_packed_corpus_scan_at_the_four_chip_cell_size(packed_scans):
    # The sharded cell's step (benchmark/configs/w2v-300-10m-x4.json): 10M
    # x 300 f32 is 24 GB of tables, 6 GB a chip over the host's four (7.68
    # at rest, in rows of 384 columns), and its corpus is resident and
    # replicated. The tables are donated and the whole must fit one chip's
    # 16 GB: what is left over is all the room a later PR has for the
    # step's temporaries.
    import re

    from glint_word2vec_tpu.corpus.batching import packed_pair_batch

    eng, compiled = packed_scans("10m-4chips")
    mem = _fits(compiled, 4)
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        2 * eng.rows_per_shard * D * 4
    ), mem
    assert mem["args"] < _table_args_ceiling(10_000_000, 4, 256 * 10**6), mem
    # ISSUE 49's parent held 303,918,592 B of temporaries here (seven
    # all-reduced blocks of 26,215 rows); the six own-row blocks of the
    # pair side still stand, ahead of the logits and d_center.
    assert mem["temp"] < 1.5e9 and mem["temp"] <= 303_918_592, mem
    # What crosses the model axis (ISSUE 51): no syn1 row. Two row blocks
    # are all-reduced, h (every shard's syn1 scatter wants every pair's)
    # and the partial d_center, and between them the pairs' logit
    # partials; each op under the scope the program gave it. Nothing is
    # reduce-scattered, gathered or permuted, so the compiler has nothing
    # to pad and re-cut in ops that carry no scope.
    pairs = packed_pair_batch(BATCH, WINDOW, 1)
    assert pairs == 26_215
    text = compiled.as_text()
    for op in ("reduce-scatter", "all-gather", "collective-permute",
               "all-to-all", "formatting steps: (pad"):
        assert op not in text, op
    reduced = [line for line in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", line)]
    exchange = [re.search(r"= (\w+)\[([\d,]+)\]\{([\d,]+)", line).groups()
                for line in reduced if "glint.exchange" in line]
    assert len(exchange) == len(reduced) - 1  # the scatters' s32 counts
    assert [e for e in exchange if e[1].endswith(f",{D_REST}")] == [
        ("f32", f"{pairs},{D_REST}", "1,0")] * 2, exchange
    # The logits cross with the pairs MINOR, as (6, 26215) lies in
    # memory, whatever order the shape is printed in: tiled (8, 128)
    # under 1 MB, where pairs-major each pair's six would pay for 128
    # lanes, 13.4 MB (ISSUE 38's rule).
    (dtype, dims, minor_to_major), = [
        e for e in exchange if not e[1].endswith(f",{D_REST}")]
    dims = [int(n) for n in dims.split(",")]
    minor, second = (dims[int(i)] for i in minor_to_major.split(","))
    assert (dtype, minor, second) == ("f32", pairs, 1 + NEG)
    assert 4 * -(-minor // 128) * 128 * -(-second // 8) * 8 < 1e6
    # The loss's order of summation (ISSUE 51's first build read
    # replay.loss_gap 1.26e-6 on the chip, ISSUE 49's the same): the step's
    # sums to a scalar under glint.grads, the loss's terms and the mask's
    # count, run over a VECTOR of the pairs in the tiles the one-chip
    # program's run over, never over a (26215, 1) column that lies in
    # tiles of (1, 128), which the chip sums in another order.
    assert _scalar_sums(text) == _scalar_sums(
        packed_scans("2m-1chip")[1].as_text()
    ) == [f"f32[{pairs}]{{0:T(1024)}}"] * 2


def _scalar_sums(text: str) -> list:
    """The operands' shapes and tiles (less the memory space the compiler
    chose for them) of a compiled packed scan's ``reduce`` ops to a float32
    scalar under ``glint.grads``."""
    import re

    shape = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\]\{[^}]*\})", text, re.M))
    return sorted(
        re.sub(r"S\(\d+\)", "", shape[m.group(1)])
        for m in re.finditer(
            r"= f32\[\]\S* reduce\((%[\w.\-]+),.*glint\.grads", text))


def test_packed_corpus_scan_at_three_million_rows_fits_one_chip(packed_scans):
    # GoogleNews-vectors-negative300's rows. Refused while the step held a
    # second pair of tables (compile check, ISSUE 24); BENCHMARK.json's
    # ``reduced: ["vocab"]`` is a benchmark issue's to lift.
    eng, compiled = packed_scans("3m-1chip")
    mem = _fits(compiled)
    assert mem["temp"] < 1.5e9, mem
    assert not _whole_table_copies(compiled, eng)


@pytest.mark.parametrize("name", ["2m-1chip", "10m-4chips"])
def test_packed_corpus_scan_draws_its_batch_without_a_branch(packed_scans,
                                                            name):
    # ISSUE 33: under glint.batch the chip's program chooses nothing at run
    # time and searches nothing a position: no conditional, no loop that
    # carries a span-wide operand (the one loop left is words_done's binary
    # search over a scalar), no gather a candidate position or a context
    # lane (the span's words and sentences are slices of the view and of
    # its per-position record).
    import re

    from glint_word2vec_tpu.corpus.batching import (
        context_width,
        packed_pair_batch,
    )

    _, compiled = packed_scans(name)
    span = -(-3 * packed_pair_batch(BATCH, WINDOW, 1)
             // context_width(WINDOW))
    lanes = span * context_width(WINDOW)
    batch = [line for line in compiled.as_text().splitlines()
             if "glint.batch" in line]
    assert len(batch) > 20  # the scope reached the compiled program
    wide = re.compile(rf"\[(?:{span}|{lanes})[,\]]")
    for line in batch:
        assert " conditional(" not in line, line[:300]
        if " while(" in line or " gather(" in line:
            assert not wide.search(line.split("metadata=")[0]), line[:300]


@pytest.mark.parametrize("name", ["2m-1chip", "10m-4chips"])
def test_packed_corpus_scan_keeps_the_tables_as_they_rest(packed_scans, name):
    # Row-major in and out by the device's own default, so donation
    # aliases and no edge copies a whole table (shard).
    eng, compiled = packed_scans(name)
    args, _ = compiled.input_formats
    outs = compiled.output_formats
    assert all(_rests(f, eng) for f in (*args[:2], *outs[:2])), (
        args[:2], outs[:2]
    )
    assert args[0].layout == outs[0].layout == args[1].layout
    assert not _whole_table_copies(compiled, eng)
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        2 * eng.rows_per_shard * D_REST * 4
    )


def _stops_at_the_corpus_end(compiled, eng) -> None:
    """ISSUE 44: the group's loop runs ``i < K and pos < n_valid`` trips, a
    count the data decides. The chip's compiler must still keep both
    tables in place through the loop's carry: no ``copy`` of anything
    with a table's rows (the callers hold the donation to its aliasing)."""
    import re

    text = compiled.as_text()
    assert re.search(r'op_name="jit\(local_(bag_)?packed_scan\)/'
                     r'(shard_map/)?while/cond/and"', text)
    rows = re.findall(
        rf"= \w+\[{eng.rows_per_shard},\d+\]\S* (?:copy|transpose)\(", text)
    assert not rows, rows


@pytest.mark.parametrize("name", ["2m-1chip", "10m-4chips"])
def test_packed_corpus_scan_stops_at_the_corpus_end(packed_scans, name):
    import inspect

    eng, compiled = packed_scans(name)
    _stops_at_the_corpus_end(compiled, eng)
    # ... and it is one program a (P, W, B, S, K, G), as it was: where the
    # corpus ends is a traced argument, not a key of the cache.
    assert list(inspect.signature(
        eng._make_packed_corpus_scan).parameters) == [
            "P", "W", "B_grid", "S", "K", "G"]


def _scatter_holds_no_slot_buffer(compiled, eng, source_rows=0) -> list:
    """The lines of the compiled program under ``glint.scatter``, after
    asserting what PR 35 took out of them: no XLA scatter at all (the run
    totals were one, the writer before PR 30 another) and no float32
    buffer of rows but the table and one chunk's payload (the totals of
    every slot were ``f32[159744,384]`` at word level, 245 MB).
    ``source_rows``: the rows of a payload SOURCE the step forms under the
    scope (the shared pool's ``h ++ d_pool``), which is no slot buffer."""
    import re

    from glint_word2vec_tpu.ops import slab_writer

    lines = [line for line in compiled.as_text().splitlines()
             if "glint.scatter" in line]
    assert len(lines) > 50  # the scope reached the compiled program
    rows = re.compile(rf"f32\[(\d+),{eng.padded_dim}\]")
    # the table, a chunk's payload, a slab's carried accumulator
    known = (eng.rows_per_shard, slab_writer.CHUNK,
             slab_writer.slab_rows("float32"), source_rows)
    for line in lines:
        result = line.split("metadata=")[0].split("(")[0]
        assert " scatter(" not in line, line[:300]
        assert not [n for n in rows.findall(result)
                    if int(n) not in known], line[:300]
    return lines


# What each program held in temporaries at PR 35's parent (compile check,
# PR 35). The totals buffer is gone from them, yet they are the parent's
# less a megabyte or two: the peak lies in the grads' buffers of
# (131,075, 384), not in the scatter's.
PARENT_TEMP = {
    "2m-1chip": 1_140_963_840, "3m-1chip": 1_140_963_840,
    "10m-4chips": 1_150_474_752, "1chip-shared_pool": 2_222_158_848,
}


@pytest.mark.parametrize(
    "name", ["2m-1chip", "3m-1chip", "10m-4chips", "1chip-shared_pool"]
)
def test_packed_corpus_scan_writes_rows_by_slabs(packed_scans, name):
    # Lowered for a TPU, a resting table's scatter ends in the slab writer
    # (ops/slab_writer.py): a Mosaic kernel for each table, filed under
    # glint.scatter, which totals the runs of the sorted slots itself, so
    # that no XLA scatter is left there, on a table (96 ns a row: PERF.md,
    # PR 26) or into a buffer of totals (21 ns a slot: PR 35).
    from glint_word2vec_tpu.corpus.batching import packed_pair_batch

    eng, compiled = packed_scans(name)
    pool = SCANS[name][1]
    lines = _scatter_holds_no_slot_buffer(
        compiled, eng, pool and packed_pair_batch(BATCH, WINDOW, 1) + pool
    )
    kernels = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line]
    assert kernels and all("glint.scatter/syn" in k for k in kernels), kernels
    for table in ("syn0", "syn1"):
        assert any(f"glint.scatter/{table}" in k for k in kernels), table
        # one sort of the slots a table (the second, which brought the
        # distinct rows to the front, went with the totals), and since
        # ISSUE 45 one of each chunk's slab keys with their tile rows, a
        # row of 4,096 a chunk: what lays the kernel's tables down a slab
        sorts = [line.split(" sort(")[0] for line in lines if " sort(" in line
                 and f"glint.scatter/{table}" in line]
        assert len(sorts) == 2, sorts
        assert len([r for r in sorts if ",4096]" not in r]) == 1, sorts
        assert len([r for r in sorts
                    if r.count("[") == 2 == r.count(",4096]")]) == 1, sorts
    assert _fits(compiled, SCANS[name][0])["temp"] < PARENT_TEMP[name]


@pytest.mark.parametrize("name", ["2m-1chip", "10m-4chips"])
def test_packed_corpus_scan_pads_no_row_tensor(packed_scans, name):
    # ISSUE 38: gathered rows keep the pair axis beside d. A float32
    # (..., 5, 384) is tiled (8, 128) and pays for 8 negatives, and the
    # reshapes to and from it were copies of every row (f32[26215,1,5,384],
    # 322 MB, three times a step); so was the cut of one flat gather into
    # f32[5,26215,384]. No array of rank 3 or more with d minor is left
    # whose second-minor axis is not whole tiles, and the step's
    # temporaries fell from 1.14 GB to 0.30 (compile check, PR 38).
    import re

    _, compiled = packed_scans(name)
    rows = re.compile(rf"f32\[(?:\d+,)+(\d+),{D_REST}\]")
    padded = {m.group(0) for m in rows.finditer(compiled.as_text())
              if int(m.group(1)) % 8}
    assert not padded, padded
    assert _fits(compiled, SCANS[name][0])["temp"] < 0.4e9


def _compile_subword_scan(engines, name):
    vocab, words = SUBWORD_SCANS[name]
    eng = engines(1, 0, vocab, BUCKET)
    sentences = -(-(words - 8 * 80_000) // 40) + 80_000
    return eng, _compile_packed_scan(eng, words, sentences, MAX_SUBWORDS)


def test_subword_packed_scan_at_the_cell_size(engines):
    # The subword cell's step (benchmark/configs/ft-300-1m-2mb.json): two
    # tables of 1M word rows + 2M bucket rows, 9.22 GB at rest, donated;
    # the (1M, 32) group table, 128 MB, replicated beside the corpus. A
    # centre's group is gathered and scattered once a run of pairs: the
    # step's temporaries grow by a few buffers of (7,866 runs x 32) rows
    # of 384 columns, 387 MB each. ISSUE 31 reckoned 11.5-12.5 GB in all.
    eng, compiled = _compile_subword_scan(engines, "ft-1m-2mb")
    mem = _fits(compiled)
    assert eng.padded_vocab == 3_000_000
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        2 * eng.rows_per_shard * D_REST * 4
    ), mem
    assert mem["total"] < 12.5e9 and mem["temp"] < 3.0e9, mem
    assert not _whole_table_copies(compiled, eng)
    text = compiled.as_text()
    assert "all-reduce" not in text
    # both tables' scatters end in the slab writer, the group's rows too
    kernels = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for table in ("syn0", "syn1"):
        assert any(f"glint.scatter/{table}" in k for k in kernels), table
    _scatter_holds_no_slot_buffer(compiled, eng)  # f32[360448,384] was one
    _stops_at_the_corpus_end(compiled, eng)
    assert "glint.compose" in text and "glint.gather/syn0" in text
    # fastText's cc.en.300 shape, 2M words + 2M buckets, which ISSUE 31
    # reckoned too large for one chip: compiled once by hand it FITS, at
    # 13,443,975,168 B with the same 828,993,024 B of temporaries
    # (compile check, PR 38, which took 376 MB of padded row tensors out
    # of the step; PR 31's read 13,793,810,432 and 1,205,386,752; a
    # second compile costs this suite a minute).
    # That is this program and what a million more words add to its
    # arguments: rows of both tables, of the group table, of the sampler's
    # two tables, and the corpus's words. PERF.md section 7 says what the
    # cut to 1M words rests on since.
    more = 1_000_000 * (2 * D_REST * 4 + MAX_SUBWORDS * 4 + 8 + 4)
    assert abs(mem["total"] + more - 13_443_975_168) < 64e6, mem
    assert mem["total"] + more < HBM_BYTES


@pytest.fixture(scope="module")
def cbow_scan(engines):
    """The CBOW cell's step (benchmark/configs/w2v-cbow-300-3m.json),
    compiled once for the module: two tables of 3M rows, 9.22 GB at rest,
    donated; 8,192 positions a step over the cell's corpus of 7,639,956
    tokens."""
    words = 3_000_000 - 44 + 4_000_000 + 8 * 80_000
    sentences = -(-(words - 8 * 80_000) // 40) + 80_000
    eng = engines(1, 0, 3_000_000, architecture="cbow")
    return eng, _compile_packed_scan(eng, words, sentences)


def test_cbow_packed_scan_at_the_cell_size(cbow_scan):
    # The 8,202 words of the step's span are one row of syn0 each and 6
    # syn1 rows a position (8,202 + 49,152 row slots where the subword
    # step has 360k + 157k, and the role-swapped form had 81,920 + 49,152).
    # ISSUE 34 reckoned 10.2-10.8 GB at a fit's peak; the program is
    # 9,357,983,744 B, 54,801,920 of them temporaries, where the ten
    # gathered blocks of the bags' rows kept about 0.4 GB (compile check,
    # PR 42).
    eng, compiled = cbow_scan
    mem = _fits(compiled)
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        2 * eng.rows_per_shard * D_REST * 4
    ), mem
    assert mem["total"] < 9.6e9 and mem["temp"] < 0.2e9, mem
    assert not _whole_table_copies(compiled, eng)
    text = compiled.as_text()
    assert "all-reduce" not in text and "packed_scan" in text
    # both tables' scatters end in the slab writer, the span's rows too
    kernels = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for table in ("syn0", "syn1"):
        assert any(f"glint.scatter/{table}" in k for k in kernels), table
    _scatter_holds_no_slot_buffer(compiled, eng)  # f32[81920,384] was one
    _stops_at_the_corpus_end(compiled, eng)
    for scope in ("glint.batch", "glint.sample", "glint.compose/group",
                  "glint.compose/bag", "glint.gather/syn0",
                  "glint.gather/syn1", "glint.grads"):
        assert scope in text, scope


def test_cbow_packed_scan_reads_each_span_row_once(cbow_scan):
    # ISSUE 42: a bag's words are named by where they stand in the span.
    # The span's 8,202 rows are gathered ONCE (the role-swapped form
    # gathered f32[81920,384], then ten blocks of f32[8192,384], a row 5.5
    # times), the bags' sums are ten shifted slices that the compiler
    # takes into the logits' fusion, and the gradient goes back through
    # ONE fusion of ten padded adds into the span, which the scatter takes
    # as 8,202 slots.
    import re

    _, compiled = cbow_scan
    text = compiled.as_text()
    gathers = re.findall(
        r"= (f32\[[\d,]+\])\S* gather\(.*glint\.gather/(syn[01])/", text)
    assert sorted(gathers) == (
        [("f32[8192,384]", "syn1")] * (1 + NEG) + [("f32[8202,384]", "syn0")]
    ), gathers
    for shape in ("[81920,384]", "[8192,10,384]", "[10,8192,384]",
                  "[8202,1,384]"):
        assert shape not in text, shape
    spread = re.findall(r"%pad_add_fusion[.\d]* = f32\[([\d,]+)\]", text)
    assert spread == ["8202,384"], spread
    assert re.search(r"s32\[8202\]\S*, f32\[8202\]\S*, s32\[8202\]\S*\) "
                     r"sort\(.*glint\.scatter/syn0", text)


def test_subword_cbow_packed_scan_at_the_cell_size(engines):
    # fastText's CBOW cell (benchmark/configs/ft-cbow-300-1m-2mb.json): the
    # subword cell's tables (1M word rows + 2M bucket rows, 9.22 GB at
    # rest, donated) and text, a (1M, 16) group table of 64 MB, 8,192
    # positions a step with 10 negatives. Each of a rank's 8,202 span
    # words is gathered and summed once (131,232 syn0 row slots where the
    # skip-gram subword step has 251,712 and a bag of whole groups would
    # have 1,310,720) and 11 syn1 rows a position (90,112 slots). ISSUE 39
    # reckoned 10.2-10.8 GB at a fit's peak.
    vocab, words = SUBWORD_SCANS["ft-1m-2mb"]
    sentences = -(-(words - 8 * 80_000) // 40) + 80_000
    eng = engines(1, 0, vocab, BUCKET, architecture="cbow", negatives=10)
    compiled = _compile_packed_scan(eng, words, sentences, 16)
    mem = _fits(compiled)
    assert eng.padded_vocab == 3_000_000
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        2 * eng.rows_per_shard * D_REST * 4
    ), mem
    # 9,695,186,432 B by part: 9,334,779,904 of arguments (9.216 GB of
    # tables, the 64 MB group table, the corpus and its record, the
    # sampler's table), 360,395,776 of temporaries (compile check, PR 39);
    # a fit peaked at 10,163,197,952 B on the chip (my chip runs, PR 39).
    assert mem["total"] < 10.2e9 and mem["temp"] < 0.6e9, mem
    assert not _whole_table_copies(compiled, eng)
    text = compiled.as_text()
    assert "all-reduce" not in text and "packed_scan" in text
    kernels = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for table in ("syn0", "syn1"):
        assert any(f"glint.scatter/{table}" in k for k in kernels), table
    _scatter_holds_no_slot_buffer(compiled, eng)
    _stops_at_the_corpus_end(compiled, eng)
    for scope in ("glint.batch", "glint.sample", "glint.compose/group",
                  "glint.compose/bag", "glint.gather/syn0",
                  "glint.gather/syn1", "glint.grads"):
        assert scope in text, scope
    # the bags read the composed words as shifted slices: no (positions x
    # lanes x d) tensor is ever formed
    assert "f32[8192,10,384]" not in text and "f32[10,8192,384]" not in text


def _fusion_roots(text: str, scope: str) -> list:
    """``(the fusion instruction's own op_name, the op_names inside it,
    joined)`` of every fused kernel of a compiled program that holds an op
    traced under ``scope``."""
    import re

    bodies, name = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            bodies[name.lstrip("%")] = []
        elif name and line.startswith("}"):
            name = None
        elif name:
            bodies[name.lstrip("%")].append(line)
    out = []
    for lines in bodies.values():
        for line in lines:
            called = re.search(r" fusion\(.*calls=%?([\w.\-]+)", line)
            if not called:
                continue
            inner = " ".join(re.findall(
                r'op_name="([^"]*)"', "\n".join(bodies.get(called[1], []))))
            if scope in inner:
                own = re.search(r'op_name="([^"]*)"', line)
                out.append((own[1] if own else "", inner))
    return out


def test_subword_cbow_scan_with_position_weights_at_the_cell_size(engines):
    # ``ft-cbow-pw-300-1m-2mb`` (ISSUE 54): the scan above with the position
    # table, f32[10,384], third among its arguments and results, carried
    # and donated with the two row tables; the weights ride the bags'
    # shifted adds, and the table's gradient is ten reductions over the
    # batch under ``glint.compose/posgrad``, no scatter.
    vocab, words = SUBWORD_SCANS["ft-1m-2mb"]
    sentences = -(-(words - 8 * 80_000) // 40) + 80_000
    eng = engines(1, 0, vocab, BUCKET, architecture="cbow", negatives=10,
                  position_lanes=2 * WINDOW)
    assert eng.table_names == ("syn0", "syn1", "posw")
    compiled = _compile_packed_scan(eng, words, sentences, 16)
    mem = _fits(compiled)
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        2 * eng.rows_per_shard * D_REST * 4 + 2 * WINDOW * D_REST * 4
    ), mem
    # 9,696,511,488 B by part (compile check, PR 54): 9,334,804,480 of
    # arguments (the sibling's and the table's 24,576, its ten rows resting
    # in sixteen), 361,696,256 of temporaries, 1,300,480 over the sibling's
    # 9,695,186,432 B program (compile check, PR 39)
    assert mem["total"] < 10.2e9 and mem["temp"] < 0.6e9, mem
    assert not _whole_table_copies(compiled, eng)
    text = compiled.as_text()
    assert "all-reduce" not in text and "packed_scan" in text
    _scatter_holds_no_slot_buffer(compiled, eng)
    _stops_at_the_corpus_end(compiled, eng)
    for scope in ("glint.compose/group", "glint.compose/bag",
                  "glint.compose/posgrad", "glint.scatter/syn0",
                  "glint.scatter/syn1"):
        assert scope in text, scope
    assert "f32[8192,10,384]" not in text and "f32[10,8192,384]" not in text
    # the table's update is dense: no scatter, no custom call under it
    assert not [line for line in text.splitlines()
                if "glint.compose/posgrad" in line
                and ("scatter(" in line or "custom-call(" in line)]
    # A kernel's time is filed under its ROOT's scope. The kernels that hold
    # an op of the table's reductions are rooted under glint.compose, so
    # ``step.compose_ms`` reads all the table costs; the one that forms the
    # positions' gradient (glint.grads ops, rooted under glint.compose/bag
    # without the weights) ends in a lane's reduction and is filed under
    # posgrad: why the cell lists no ``step.bag_ms`` (PERF.md section 3).
    roots = _fusion_roots(text, "glint.compose/posgrad")
    assert roots and all("glint.compose" in r for r, _ in roots), roots
    assert any("glint.compose/posgrad" in r and "glint.grads" in inner
               for r, inner in roots), roots


def test_slab_writer_compiles_for_bfloat16(topo):
    # The kernel alone over a 2M x 384 bfloat16 table, (16, 384) slabs, in
    # place. A single-sublane load at a traced offset is refused there
    # ("cannot statically prove that index in dimension 1 is a multiple of
    # 8"); the kernel loads the whole slab and selects the sublane.
    import jax
    import jax.numpy as jnp

    from jax.sharding import SingleDeviceSharding

    from glint_word2vec_tpu.ops import slab_writer

    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n = 157_290  # syn1's update slots of the benchmark's step
    compiled = jax.jit(slab_writer.write, donate_argnums=0).lower(
        sds((2_000_000, D_REST), jnp.bfloat16), sds((n,), jnp.int32),
        sds((n,), jnp.float32), sds((26_215, D_REST), jnp.float32),
        sds((n,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 2_000_000 * D_REST * 2
    assert m.temp_size_in_bytes < 10**6, m


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("width", [128, 384, 1024, 2048])
def test_slab_writer_compiles_at_width(topo, width, dtype):
    # The kernel alone over a 3 GB table (1.5 in bfloat16) of each width
    # the engine can rest a table in, with the tables ISSUE 45 lays down a
    # slab in SMEM: a chunk's tile rows, first slots and sublanes, three
    # int32 a slot (48 KB at the 4,096 slots a 384-column table takes, 6
    # KB at the 512 of 2,048 columns), where the parent's rows and hops
    # were two. The table goes in and comes out as (tile rows, sub, width)
    # and no copy of it is made on the way.
    import re

    import jax
    import jax.numpy as jnp

    from jax.sharding import SingleDeviceSharding

    from glint_word2vec_tpu.ops import slab_writer

    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n = 157_290  # syn1's update slots of the benchmark's step
    rows = 2_000_000 * D_REST // width // 16 * 16
    itemsize = jnp.dtype(dtype).itemsize
    compiled = jax.jit(slab_writer.write, donate_argnums=0).lower(
        sds((rows, width), dtype), sds((n,), jnp.int32),
        sds((n,), jnp.float32), sds((26_215, width), jnp.float32),
        sds((n,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 1
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == rows * width * itemsize
    # a chunk's payload and the bookkeeping of every chunk, no more
    assert m.temp_size_in_bytes < 2 * slab_writer.PAYLOAD_BYTES, m
    sub = slab_writer.slab_rows(dtype)
    table = (rf"(?:f32|bf16)\[(?:{rows},{width}|{rows // sub},{sub},{width})\]")
    assert not [line[:200] for line in text.splitlines()
                if re.search(rf"= {table}\S* (?:copy|transpose)\(", line)]


def test_subsample_compact_compiles(engines):
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.ops.device_batching import subsample_compact

    sds = _shapes(engines(1))
    compiled = jax.jit(subsample_compact).lower(
        sds((CORPUS_WORDS,), jnp.int32),
        sds((CORPUS_SENTENCES + 1,), jnp.int32),
        sds((V,), jnp.float32), sds((2,), jnp.uint32),
    ).compile()
    _fits(compiled)


# The serving warm-up family (ModelServer defaults: Q buckets 1..64, k
# buckets 16/32, sentence grid 16 x 64): its corners, not all ~50 shapes —
# the single-query top-k alone takes the compiler half a minute at V=1M.
@pytest.mark.parametrize(
    "chips,op,shape",
    [
        (1, "topk", (16,)),
        (1, "topk_batch", (1, 16)),
        (1, "topk_batch", (64, 32)),
        (4, "topk_batch", (8, 32)),
        (1, "pull", (64,)),
        (1, "pull_average", (16, 64)),
        (4, "pull_average", (16, 64)),
        (1, "norms", ()),
    ],
    ids=lambda v: (
        v if isinstance(v, str)
        else f"{v}chip" if isinstance(v, int)
        else "x".join(map(str, v)) or "-"
    ),
)
def test_query_program_compiles(engines, chips, op, shape):
    eng = engines(chips)
    _fits(_lower_query(eng, op, shape).compile(), chips)


def _lower_query(eng, op, shape):
    import jax.numpy as jnp

    sds = _shapes(eng)
    table = _table(eng)
    norms = sds((eng.padded_vocab,), jnp.float32, "model")
    nq = sds((), jnp.int32)
    if op == "topk":
        lowered = eng._make_topk(shape[0]).lower(
            table, sds((eng.padded_dim,), jnp.float32), norms, nq
        )
    elif op == "topk_batch":
        lowered = eng._make_topk_batch(shape[1]).lower(
            table, sds((shape[0], eng.padded_dim), jnp.float32),
            sds((shape[0],), jnp.int32), norms, nq
        )
    elif op == "pull":
        lowered = eng._pull.lower(table, sds(shape, jnp.int32))
    elif op == "pull_average":
        lowered = eng._pull_average.lower(
            table, sds(shape, jnp.int32), sds(shape, jnp.float32)
        )
    else:
        lowered = eng._norms.lower(table)
    return lowered


# The serving cell's round (benchmark/traffic/w2v-300-2m.synonyms.json: 16
# callers, num 10 -> the k bucket 16): the pull of the coalesced words'
# rows, then one batch top-k. Until PR 28 the pull copied the whole 2.4 GB
# table to reach 16 rows (3.07 GB of temporaries).
@pytest.mark.parametrize(
    "op,shape,temp_ceiling",
    [("pull", (16,), 1 * 10**6),
     ("pull_average", (16, 64), 1 * 10**6),
     ("topk_batch", (16, 16), 200 * 10**6)],
    ids=["pull", "pull_average", "topk_batch"],
)
def test_query_program_at_the_benchmark_size_copies_no_table(
    engines, op, shape, temp_ceiling
):
    eng = engines(1, vocab=2_000_000)
    compiled = _lower_query(eng, op, shape).compile()
    mem = _fits(compiled)
    assert _rests(compiled.input_formats[0][0], eng)
    assert not _whole_table_copies(compiled, eng)
    assert mem["temp"] < temp_ceiling, mem


# The four-chip served cell (benchmark/configs/w2v-nn-300-10m-x4.json: 10M x
# 300 by rows over four chips, 16 callers, num 10 -> the k bucket 16): a
# round's pull and batch top-k, and the program that fills the table a block
# of 250,000 rows at a time (``write_rows``: each shard writes its own rows;
# as one ``dynamic_update_slice`` the partitioner gathered the whole table on
# every chip, 14.66 GB of temporaries, and the compiler refused it: PR 47).
# ISSUE 47's sizes: two tables, 30.72 GB at rest, 7.68 GB a chip; a program
# is handed ONE of them, 3.84 GB a chip, and copies none of it.
X4_TABLE_BYTES_A_CHIP = 10_000_000 * D_REST * 4 // 4


@pytest.mark.parametrize(
    "op,shape,temp_ceiling",
    [("pull", (16,), 1 * 10**6),
     ("topk_batch", (16, 16), 200 * 10**6),
     ("topk_batch", (64, 32), 1400 * 10**6),
     ("norms", (), 1 * 10**6),
     ("write_rows", (250_000,), 800 * 10**6)],
    ids=["pull", "topk_batch-16x16", "topk_batch-64x32", "norms",
         "write_rows"],
)
def test_query_program_at_the_four_chip_served_cell_size(
    engines, op, shape, temp_ceiling
):
    import jax.numpy as jnp

    eng = engines(4, vocab=10_000_000)
    assert eng.rows_per_shard == 2_500_000
    if op == "write_rows":
        sds = _shapes(eng)
        lowered = eng._row_writer().lower(
            _table(eng), sds((shape[0], D), jnp.float32), sds((), jnp.int32)
        )
    else:
        lowered = _lower_query(eng, op, shape)
    compiled = lowered.compile()
    mem = _fits(compiled, 4)
    assert _rests(compiled.input_formats[0][0], eng)
    assert not _whole_table_copies(compiled, eng)
    assert mem["temp"] < temp_ceiling, mem
    # a device holds its quarter of the one table the program is handed
    # (half of the issue's 7.68 GB of tables a chip) and little else
    assert 2 * X4_TABLE_BYTES_A_CHIP == 7_680_000_000
    assert X4_TABLE_BYTES_A_CHIP <= mem["args"] < (
        X4_TABLE_BYTES_A_CHIP + 320 * 10**6), mem
    if op == "write_rows":  # in place: the shard that comes back is the
        assert mem["aliased"] == X4_TABLE_BYTES_A_CHIP, mem  # one handed in


# The served subword cell's compose (benchmark/configs/ft-nn-300-1m-2mb.json:
# 1M words + 2M bucket rows, groups 16 wide): a coalesced round's
# out-of-dictionary words are ONE pull-average at their power-of-two bucket
# (PR 43), one word through /vector the bucket of 1, the composed table's
# build and the bulk paths the whole block of 4,096. None may copy the
# 4.6 GB table to reach its rows.
@pytest.mark.parametrize("rows", [1, 64, 4096])
def test_compose_bucket_at_the_served_subword_cell_size(engines, rows):
    eng = engines(1, vocab=1_000_000, extra_rows=BUCKET)
    compiled = _lower_query(eng, "pull_average", (rows, 16)).compile()
    mem = _fits(compiled)
    assert _rests(compiled.input_formats[0][0], eng)
    assert not _whole_table_copies(compiled, eng)
    # the gathered slots, twice over (the gather and its masked product)
    assert mem["temp"] < 2 * rows * 16 * D_REST * 4 + 10**6, mem

