"""v5e compile rehearsals of the main-path device programs.

Each test compiles one program at its real shapes for a described (not
attached) TPU v5e chip: the TPU compiler is installed here, so what the
chip's compiler would refuse — a Pallas BlockSpec off the (8, 128)
tiling, a kernel over its VMEM, a program over HBM — fails here first,
at no chip time. Nothing runs, so nothing here says anything about
results or speed. Code that branches on `jax.default_backend()` is
steered to its TPU branch inside the test (the CPU backend is what JAX
reports in this process).

The topology is described only inside the module fixture below, never
at import: one process at a time may load the TPU library, and the
driver's workers all import every test file.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")

#: v5e HBM per chip
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the programs compile as the main path runs them, with 64-bit types
    # off: a fused POA build earlier in this process turns them on for
    # good (ops/poa_fused.py), and the Pallas kernels' lowering then
    # recurses without end
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_x64", prev_x64)
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_branch(monkeypatch):
    """Trace the programs as the chip would: their TPU branches."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, sharding, *shapes):
    compiled = fn.lower(*(_spec(sharding, s, d) for s, d in shapes)).compile()
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def _session_shapes(n, length, p, b):
    return [((b, n), jnp.int8), ((b, n, p), jnp.int16), ((b, n), jnp.int16),
            ((b, n), jnp.uint8), ((b, length), jnp.int8), ((b,), jnp.int32),
            ((b,), jnp.int32)]


def test_session_program_compiles_for_v5e(one_chip, tpu_branch):
    from racon_tpu.ops.poa_graph import MAX_PRED, RING, graph_aligner

    fn = graph_aligner.__wrapped__(320, 256, MAX_PRED, 3, -5, -4, ring=RING)
    _compile(fn, one_chip, *_session_shapes(320, 256, MAX_PRED, 8))


@pytest.mark.parametrize("score_dtype", ["int32", "int16"])
def test_pallas_window_sweep_largest_bucket_compiles(one_chip, score_dtype):
    from racon_tpu.ops.poa_graph import MAX_LEN, MAX_NODES, MAX_PRED
    from racon_tpu.ops.poa_pallas import fits_vmem, window_sweep

    assert fits_vmem(MAX_NODES, MAX_LEN, MAX_PRED, score_dtype)
    fn = window_sweep.__wrapped__(MAX_NODES, MAX_LEN, MAX_PRED, 3, -5, -4,
                                  interpret=False, score_dtype=score_dtype)
    compiled = _compile(
        fn, one_chip,
        *_session_shapes(MAX_NODES, MAX_LEN, MAX_PRED, 8),
        ((8,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("score_dtype", ["int32", "int16"])
def test_pallas_wavefront_largest_bucket_compiles(one_chip, score_dtype):
    from racon_tpu.ops import align_pallas

    # the largest bucket the auto band rule reaches that fits: edge
    # 8192 at 10% band (16384 at its 1664 band does not)
    edge, band = 8192, 896
    assert align_pallas.fits_vmem(edge, band, score_dtype)
    assert not align_pallas.fits_vmem(16384, 1664, score_dtype)
    lq, lt = align_pallas.ext_widths(edge, band)
    fn = align_pallas.wavefront_align.__wrapped__(edge, band, score_dtype,
                                                  False, interpret=False)
    compiled = _compile(fn, one_chip, ((8, lq), jnp.int8),
                        ((8, lt), jnp.int8), ((8,), jnp.int32),
                        ((8,), jnp.int32), ((8, 2 * edge + 1), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def _loop_cycles(hlo: str) -> dict[str, int]:
    """Each `while` of an optimized program: its tuple type, mapped to
    the compiler's `estimated_cycles` summed over its body."""
    bodies, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
        if m and line.rstrip().endswith("{"):
            name = m.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            bodies[name].append(line)
    return {line.split(" while(")[0]: sum(
                int(c) for c in re.findall(r'"estimated_cycles":"(\d+)"',
                                           "\n".join(bodies[body])))
            for lines in bodies.values() for line in lines
            if " while(" in line
            for body in re.findall(r"body=(%[\w.\-]+)", line)}


@pytest.mark.parametrize("edge, band", [(8192, 896), (32768, 2048)])
def test_xla_aligner_edge_compiles_for_v5e(one_chip, edge, band):
    """The bucket ~8 kb ONT overlaps land in, and the longest reads' one,
    at the batch width the backpointer budget gives. The 2-bit operands
    are unpacked once, before the wavefront scan: no loop carries them
    packed, and the forward loop costs what the int8 program's does."""
    from racon_tpu.ops.align import BatchAligner, _kernel_for

    n_waves = 2 * edge + 1
    lanes = BatchAligner()._lane_cap(n_waves, band, 1)
    rest = (((lanes,), jnp.int32), ((lanes,), jnp.int32),
            ((lanes, n_waves), jnp.int32))
    loops = {}
    for packed, width, dtype in ((True, edge // 4, jnp.uint8),
                                 (False, edge, jnp.int8)):
        fn = _kernel_for.__wrapped__(band, n_waves, "int32", packed)
        compiled = _compile(fn, one_chip, ((lanes, width), dtype),
                            ((lanes, width), dtype), *rest)
        loops[packed] = _loop_cycles(compiled.as_text())
    packed_operand = f"u8[{lanes},{edge // 4}]"
    assert loops[True] and not any(packed_operand in carry
                                   for carry in loops[True])
    # the forward scan is the costlier of the two loops in both programs
    fwd_packed = max(loops[True].values())
    fwd_int8 = max(loops[False].values())
    assert fwd_int8 > 0
    assert abs(fwd_packed - fwd_int8) <= 0.1 * fwd_int8, (fwd_packed,
                                                          fwd_int8)


def test_fused_depth_bucket_compiles_for_v5e(one_chip, tpu_branch):
    """The smallest session bucket's widths as the fused envelope: the
    fused program's compile grows with its node envelope (about 20 s
    here at (320, 256), 70-110 s at the production (2048, 640) — see
    CHANGES.md, PR 21), and that one is rehearsed outside the suite."""
    from racon_tpu.ops.poa_fused import DEPTH_BUCKETS, fused_builder
    from racon_tpu.ops.poa_graph import MAX_PRED

    n, length, d, b = 320, 256, DEPTH_BUCKETS[0], 8
    fn = fused_builder.__wrapped__(n, length, d, MAX_PRED, 3, -5, -4)
    state = [((b, n), jnp.int8), ((b, n, MAX_PRED), jnp.int16),
             ((b, n, MAX_PRED), jnp.int32), ((b, n), jnp.int32),
             ((b, n), jnp.int16), ((b, n), jnp.int64),
             ((b, n, 5), jnp.int16), ((b, n), jnp.int16), ((b,), jnp.int32),
             ((b,), jnp.int32), ((b,), np.bool_)]
    layers = [((b, d, length), jnp.int8), ((b, d), jnp.int32),
              ((b, d, length), jnp.int8), ((b, d), jnp.int16),
              ((b, d), jnp.int16), ((b, d), jnp.int32), ((b,), jnp.int32)]
    _compile(fn, one_chip, *state, *layers)
