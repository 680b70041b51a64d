"""Serve steps compiled for a described TPU v5e chip at serve widths
(B=64, L=65536) — what the chip's compiler refuses fails here, with no
chip attached.

The v5e topology is described inside a module fixture, never at import:
only the pytest worker that runs this file loads the TPU compiler.
Nothing runs; the compiled programs are only inspected. The persistent
compilation cache is off around these compiles (a cache entry written
for a described chip cannot be read back without one)."""

import numpy as np
import pytest

from repro.core.index_builder import build_index
from repro.core.jax_search import (
    compress_qt1_batch,
    make_qt1_serve_step,
    make_qt1_serve_step_compressed,
    make_wv_serve_step,
    pack_qt1_batch,
    pack_qt5_batch,
)
from repro.data.corpus import generate_corpus, sample_typed_queries

B, L = 64, 65536
D = 5


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
def one_chip_mesh(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def batches():
    """Real packed batches at serve widths over a small index: the
    shapes and dtypes the executors hand the steps."""
    table, lex = generate_corpus(n_docs=80, mean_doc_len=70, vocab_size=500,
                                 seed=11)
    lex.sw_count, lex.fu_count = 14, 30
    idx = build_index(table, lex, max_distance=D)
    # padded to B with empty queries, as the executors pad a batch
    q1, q5 = (sample_typed_queries(table, lex, B, kind, window=D, seed=3)
              for kind in ("qt1", "qt5"))
    q1, q5 = (q + [[]] * (B - len(q)) for q in (q1, q5))
    return {"qt1": pack_qt1_batch(idx, q1, L=L, K=2),
            "qt5": pack_qt5_batch(idx, q5, L=L, Kn=3, Ks=3)}


def _shapes(args):
    import jax

    return tuple(jax.ShapeDtypeStruct(np.shape(a), a.dtype) for a in args)


def _compile(step, args):
    compiled = step.lower(*_shapes(args)).compile()
    mem = compiled.memory_analysis()
    # one program's arguments and temporaries fit one chip's 16 GB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    return compiled


def test_qt1_raw_step_compiles_for_v5e(one_chip_mesh, batches):
    batch = batches["qt1"]
    assert batch.key_g.shape == (B, 2, L)
    hlo = _compile(make_qt1_serve_step(one_chip_mesh, top_k=16),
                   batch.device_args()).as_text()
    # the merge join is straight-line: no search loop left on the chip
    assert "while" not in hlo


def test_qt1_delta16_step_compiles_for_v5e(one_chip_mesh, batches):
    args = compress_qt1_batch(batches["qt1"], delta_g=True)
    step = make_qt1_serve_step_compressed(one_chip_mesh, top_k=16,
                                          delta_g=True)
    assert "while" not in _compile(step, args).as_text()


@pytest.mark.parametrize("use_pallas", [False, True])
def test_qt5_raw_step_compiles_for_v5e(one_chip_mesh, batches, use_pallas):
    batch = batches["qt5"]
    assert batch.ns_g.shape == (B, 3, L)
    step = make_wv_serve_step(one_chip_mesh, "qt5", top_k=16, payload="raw",
                              max_distance=D, use_pallas=use_pallas)
    hlo = _compile(step, batch.device_args()).as_text()
    # the kernel itself, compiled by Mosaic: interpret mode cannot pass
    assert ("tpu_custom_call" in hlo) == use_pallas
