"""The main path compiled for a described TPU v5e chip, at the shapes
``chip_smoke.py`` runs: the Pallas kernels (the held-experts kernel at
Moonlight-16B-A3B's widths), the fused and the encrypted coded rounds and
the coded serve step.

Nothing here runs.  Each test lowers one program with abstract arguments
placed on one chip of a ``v5e:2x2`` topology and compiles it with the
TPU's own compiler, which refuses what interpret mode accepts: unaligned
tiles, more VMEM than a kernel may use, programs that do not fit HBM.
Each asserts that the Pallas kernel is in the compiled program
(``tpu_custom_call``) and that the program fits the chip's 16 GB.

The topology is described inside the ``topo`` fixture, never at import:
only one process at a time may load the TPU library.  The kernel
dispatch in ``kernels.ops`` asks ``jax.default_backend()``, which is the
CPU here, so the tests steer it onto its TPU branch themselves.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import ClusterSpec, CryptoSpec, Session
from repro.kernels import ops
from repro.kernels.berrut_encode import berrut_encode_kernel
from repro.kernels.coded_matmul import coded_matmul_kernel
from repro.kernels.held_experts import held_experts_kernel
from repro.kernels.mask_add import mask_add_kernel
from repro.runtime import engine

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

HBM_BYTES = 16 * 10 ** 9                   # one v5e chip
ROWS, D, N_OUT = chip_smoke.ROUND_SHAPE
N, J = 30, 27                              # fig-3 SPACDC: N, K + T
BLK = ROWS // 24


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compiles for a described chip are written to the persistent
        # cache but cannot be read back without one: keep them out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Send ``kernels.ops`` down its TPU branch: compiled Pallas kernels,
    interpret mode off."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def shaped(sharding, *arrays_or_shapes):
    """Abstract arguments on the described chip."""
    out = []
    for x in arrays_or_shapes:
        shape, dtype = ((x.shape, x.dtype) if hasattr(x, "dtype")
                        else (x, jnp.float32))
        out.append(jax.ShapeDtypeStruct(shape, dtype, sharding=sharding))
    return out


def check_compiled(compiled, min_kernels=1):
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= min_kernels
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert total <= HBM_BYTES, total
    return total


# --------------------------------------------------------------- kernels

def test_coded_matmul_kernel_compiles(one_chip):
    args = shaped(one_chip, (N, J), (J, BLK, D), (D, N_OUT))
    check_compiled(coded_matmul_kernel.lower(*args, interpret=False)
                   .compile())


def test_berrut_encode_kernel_compiles(one_chip):
    # serving's weight encode: N=8 shards of a qwen2-7b FFN weight split
    # into K=4 blocks + T=1 noise block (ServingCode, coded_layers="all")
    m = (18944 // 4) * 3584
    args = shaped(one_chip, (8, 5), (5, m))
    check_compiled(berrut_encode_kernel.lower(*args, interpret=False)
                   .compile())


def test_mask_add_kernel_compiles(one_chip):
    from repro.crypto import CURVE_SECP256K1
    from repro.crypto import field as F
    q_limbs = tuple(int(v) for v in F.int_to_limbs(CURVE_SECP256K1.q, 8))
    limbs = jax.ShapeDtypeStruct((65536, 8), jnp.uint32, sharding=one_chip)
    check_compiled(mask_add_kernel.lower(limbs, limbs, q_limbs=q_limbs,
                                         interpret=False).compile())


def test_held_experts_kernel_compiles(one_chip):
    # Moonlight-16B-A3B on one expert-parallel chip: 128 bf16 rows, 8
    # held experts of 2048 x 1408, float32 weights
    e, d, f = 8, 2048, 1408
    args = shaped(one_chip, jax.ShapeDtypeStruct((128, d), jnp.bfloat16),
                  (128, e), (e, d, f), (e, d, f), (e, f, d))
    check_compiled(held_experts_kernel.lower(*args, interpret=False)
                   .compile())


# ---------------------------------------------------------- round programs

def fig3_engine(**crypto):
    spec = ClusterSpec.paper_fig3()
    spec = dataclasses.replace(
        spec, code=dataclasses.replace(spec.code, use_kernel=True),
        crypto=CryptoSpec(**crypto) if crypto else spec.crypto)
    return Session(spec)


def test_fused_round_compiles(one_chip, on_tpu):
    with fig3_engine() as s:
        fn = s.engine._fused_fn((ROWS, D), (D, N_OUT), "float32")
        args = shaped(one_chip, (ROWS, D), (D, N_OUT), (N,))
        check_compiled(fn.lower(*args).compile())


def test_fused_round_in_row_chunks_compiles(one_chip, on_tpu, monkeypatch):
    # the 37.7 MB product leaves the program as the TPU's eight row chunks
    monkeypatch.setattr(engine, "_CHUNK_PLATFORMS", (jax.default_backend(),))
    with fig3_engine() as s:
        fn = s.engine._fused_fn((ROWS, D), (D, N_OUT), "float32")
        args = shaped(one_chip, (ROWS, D), (D, N_OUT), (N,))
        compiled = fn.lower(*args).compile()
    check_compiled(compiled)
    assert [o.shape for o in compiled.out_info] == [(ROWS // 8, N_OUT)] * 8


@pytest.mark.parametrize("mode", ["paper", "stream"])
def test_encrypted_round_compiles(one_chip, on_tpu, mode):
    with fig3_engine(encrypt="real", cipher_mode=mode) as s:
        eng = s.engine
        fn = eng._fused_real_fn((ROWS, D), (D, N_OUT), "float32")
        mat_out, mat_back = (np.asarray(m)
                             for m in eng._fused_mask_material())
        args = shaped(one_chip, (ROWS, D), (D, N_OUT), (N,), mat_out,
                      mat_back)
        # the encode and worker halves of the coded_matmul kernel
        check_compiled(fn.lower(*args).compile(), min_kernels=2)


def test_coded_serve_step_compiles(one_chip, on_tpu):
    """chip_smoke's phase B step: qwen2-7b at published widths cut to one
    chip's share, every projection coded, bucket 4."""
    from repro.models import build_model
    from repro.models.coded import build_coded_logits, encode_serving_weights
    cfg = chip_smoke.chip_share_config()
    model = build_model(cfg)
    spec = ClusterSpec.serve_deadline(coded_layers="all", max_slots=4)
    with Session(spec) as s:
        scheme = s.engine.scheme
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        built = {}

        def encode(p):
            built["code"] = encode_serving_weights(scheme, model, p, "all")
            return built["code"].arrays

        arrays = jax.eval_shape(encode, params)
        code = dataclasses.replace(built["code"], arrays=arrays)
        logits = build_coded_logits(model, scheme, code)

        def step(*args):
            out, cache, _ = logits(*args)
            return jnp.argmax(out, axis=-1).astype(jnp.int32), cache

        cache = jax.eval_shape(lambda: model.init_cache(4, 40))
        on_chip = lambda tree: jax.tree.map(       # noqa: E731
            lambda x: shaped(one_chip, x)[0], tree)
        args = (on_chip(params), on_chip(cache),
                *shaped(one_chip, np.zeros((4, 1), np.int32),
                        np.zeros((4,), np.int32), np.zeros((8,), np.float32)),
                on_chip(arrays), {})
        check_compiled(jax.jit(step).lower(*args).compile())
