"""Compile the main-path kernels for a described TPU v5e, without a chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached: it refuses what interpret mode accepts (value-
level dynamic slices, more VMEM than a kernel may use, kernel outputs the
shard_map varying-axis checker cannot type).  Every compiled program must
hold the Pallas kernel (``tpu_custom_call``).  Nothing runs, so these
tests say nothing about results or times.

The topology is described in a module fixture, never at import: only the
test process that runs this file loads the TPU library.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import (cholesky_block_pallas, matmul_pallas,
                           trsm_diag_inv_pallas, trsm_diag_pallas)


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host; the persistent compilation cache is off
    while it is in use (entries compiled for a described chip cannot be
    read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def restore():
        jax.config.update("jax_enable_compilation_cache", cache_on)
        cc.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        restore()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    restore()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_matmul_default_tiles(one_chip, dtype):
    a = _on(one_chip, (1024, 2048), dtype)
    b = _on(one_chip, (2048, 1024), dtype)
    text = _compiled_text(
        lambda a, b: matmul_pallas(a, b, bm=256, bn=256, bk=512), a, b)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_matmul_model_tiles_fit_vmem(one_chip, dtype):
    """The tile the v5e profile's kernel model picks for the n = 16384
    local block compiles under the VMEM limit the model plans against."""
    from repro.core.machine import TPU_V5E
    from repro.perf.kernel import tiles_for_plan

    name = jnp.dtype(dtype).name
    t = tiles_for_plan(TPU_V5E, "summa", 16384, 1, name)["matmul"]
    n = 2 * max(t.values())
    a = _on(one_chip, (n, n), dtype)
    text = _compiled_text(
        lambda a, b: matmul_pallas(a, b, bm=t["bm"], bn=t["bn"],
                                   bk=t["bk"]), a, a)
    assert "tpu_custom_call" in text


def _largest_admitted(kernel, shape, itemsize):
    """The candidate tiles the v5e model's VMEM gate admits that no other
    admitted candidate contains: if these compile, every admitted tile
    does."""
    import numpy as np

    from repro.core.machine import TPU_V5E
    from repro.perf.kernel import KernelModel, candidate_tiles

    cands = candidate_tiles(kernel, shape)
    ok = KernelModel(TPU_V5E).feasible(kernel, shape, cands, itemsize)
    tiles = np.stack([cands[d] for d in cands], axis=1)[ok]
    return [tuple(int(v) for v in t) for t in tiles
            if not any((u >= t).all() and (u > t).any() for u in tiles)]


LARGEST_MATMUL = [(dt, t) for dt in DTYPES
                  for t in _largest_admitted("matmul", (16384,) * 3,
                                             jnp.dtype(dt).itemsize)]


@pytest.mark.parametrize(
    "dtype,tile", LARGEST_MATMUL,
    ids=[f"{jnp.dtype(dt).name}-{'x'.join(map(str, t))}"
         for dt, t in LARGEST_MATMUL])
def test_matmul_largest_admitted_tiles_compile(one_chip, dtype, tile):
    """Every tile at the edge of what the VMEM gate admits compiles under
    the chip's scoped-VMEM limit."""
    bm, bn, bk = tile
    a = _on(one_chip, (2 * bm, 2 * bk), dtype)
    b = _on(one_chip, (2 * bk, 2 * bn), dtype)
    text = _compiled_text(
        lambda a, b: matmul_pallas(a, b, bm=bm, bn=bn, bk=bk), a, b)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["trsm", "trsm_inverse", "cholesky"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_diag_largest_admitted_block_compiles(one_chip, kernel, dtype):
    """The largest diagonal block the VMEM gate admits compiles, as a
    substitution, as a batched inversion of two blocks, and as a
    factor."""
    shape = (16384,) if kernel == "cholesky" else (16384, 16384)
    ((nb,),) = _largest_admitted(kernel.split("_")[0], shape,
                                 jnp.dtype(dtype).itemsize)
    u = _on(one_chip, (nb, nb), dtype)
    if kernel == "trsm":
        text = _compiled_text(trsm_diag_pallas, u, _on(one_chip, (1024, nb),
                                                        dtype))
    elif kernel == "trsm_inverse":
        text = _compiled_text(
            lambda u: trsm_diag_inv_pallas(u, block=nb),
            _on(one_chip, (2 * nb, 2 * nb), dtype))
    else:
        text = _compiled_text(cholesky_block_pallas, u)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_trsm_diag_block_256(one_chip, dtype):
    u = _on(one_chip, (256, 256), dtype)
    b = _on(one_chip, (1024, 256), dtype)
    assert "tpu_custom_call" in _compiled_text(trsm_diag_pallas, u, b)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_cholesky_block_256(one_chip, dtype):
    a = _on(one_chip, (256, 256), dtype)
    assert "tpu_custom_call" in _compiled_text(cholesky_block_pallas, a)


@pytest.mark.parametrize("op", ["trsm", "cholesky"])
def test_blocked_factorization_n2048(one_chip, op):
    """The blocked wrappers compile every kernel they compose, with the
    interpreter off, at n = 2048."""
    from repro.kernels import cholesky, trsm

    x = _on(one_chip, (2048, 2048), jnp.float32)
    if op == "trsm":
        text = _compiled_text(lambda u, b: trsm(u, b, interpret=False), x, x)
    else:
        text = _compiled_text(lambda a: cholesky(a, interpret=False), x)
    assert text.count("tpu_custom_call") >= 2 * (2048 // 256) - 1


def _custom_calls(text: str) -> list:
    """(kernel name, first operand's rows, its columns) of each Pallas
    kernel in a compiled program."""
    return [(name, int(r), int(k)) for name, r, k in re.findall(
        r"%(\w+)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{\w+\[(\d+),(\d+)\]", text)]


def test_trsm_recursive_blocking_n4096(one_chip):
    """The blocked TRSM at n = 4096 with the plan's tiles (block 128;
    matmul 512/1024/256) halves recursively and inverts its diagonal
    blocks: one batched ``trsm`` kernel inverts all 32 (it reads U), 32
    matmuls of contraction 128 apply the inverses, and 31 update dgemms
    contract over 2048 once, 1024 twice, ... 128 sixteen times.  Before
    the inverses there were 32 ``trsm`` substitutions over every row of B
    and the 31 dgemms.  XLA's bytes accessed were 3.75e9 (55.9 m·n·4) for
    the right-looking loop over the blocks and 1.24e9 (18.5 m·n·4) for
    recursive halving, with or without the inverses: the applies add no
    elementwise pass over B."""
    from repro.kernels import TilePlan, trsm

    n = 4096
    x = _on(one_chip, (n, n), jnp.float32)
    compiled = jax.jit(lambda u, b: trsm(
        u, b, interpret=False, tiles=TilePlan.make("trsm", block=128),
        mm_tiles=TilePlan.make("matmul", bm=512, bn=1024, bk=256))).lower(
            x, x).compile()
    calls = _custom_calls(compiled.as_text())
    assert [c for c in calls if c[0] == "trsm"] == [("trsm", n, n)]
    contractions = sorted((k for name, _, k in calls if name == "matmul"),
                          reverse=True)
    dgemms = [n // 2 ** (i + 1) for i in range(5) for _ in range(2 ** i)]
    assert contractions == sorted(dgemms + [128] * 32, reverse=True)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 24 * n * n * 4


@pytest.mark.parametrize("rows,inverted", [(15872, True), (512, False)])
def test_cholesky_panel_solve(one_chip, rows, inverted):
    """One panel of Cholesky at n = 16384, block 512, as its wrapper
    calls it: the first panel (15,872 rows) inverts L_jj^T in one
    ``trsm`` kernel on its 512 rows and applies it in one matmul of
    contraction 512; the last (512 rows) substitutes every row."""
    from repro.kernels import TilePlan, trsm

    u = _on(one_chip, (512, 512), jnp.float32)
    b = _on(one_chip, (rows, 512), jnp.float32)
    text = _compiled_text(lambda u, b: trsm(
        u, b, block=512, interpret=False,
        mm_tiles=TilePlan.make("matmul", bm=512, bn=1024, bk=256)), u, b)
    calls = _custom_calls(text)
    if inverted:
        assert calls.count(("trsm", 512, 512)) == 1
        assert calls.count(("matmul", rows, 512)) == 1 and len(calls) == 2
    else:
        assert calls == [("trsm", 512, 512)]


def _kernel_names(text: str) -> set:
    """The names of a compiled program's Pallas kernels, without their
    numeric suffix (what a profiler trace names the operations)."""
    return {re.sub(r"\.\d+$", "", m.group(1)) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)}


@pytest.mark.parametrize("kernel,want", [
    ("matmul", {"matmul"}), ("trsm", {"trsm"}), ("cholesky", {"cholesky"}),
    ("blocked_cholesky", {"matmul", "trsm", "cholesky"})])
def test_kernel_names_in_the_compiled_program(one_chip, kernel, want):
    """Each kernel keeps its name whatever function calls it: the trace
    readers (``kernel.matmul_roofline``, ``kernel.diag_share``) find the
    kernels by it."""
    from repro.kernels import cholesky

    x = _on(one_chip, (512, 512), jnp.float32)
    fns = {"matmul": (lambda a, b: matmul_pallas(a, b, bm=256, bn=256,
                                                 bk=256) * 2, (x, x)),
           "trsm": (lambda u, b: trsm_diag_pallas(u, b) * 2,
                    (_on(one_chip, (256, 256), jnp.float32),) * 2),
           "cholesky": (lambda a: cholesky_block_pallas(a) * 2,
                        (_on(one_chip, (256, 256), jnp.float32),)),
           "blocked_cholesky": (lambda a: cholesky(a, interpret=False),
                                (x,))}
    fn, args = fns[kernel]
    assert _kernel_names(_compiled_text(fn, *args)) == want


def test_summa_2d_executor_with_pallas_locals(topo):
    """The dispatched SUMMA 2D executor with Pallas local matmuls compiles
    for a 2x2 mesh of described chips (Pallas locals inside shard_map)."""
    from repro.tuner import Tuner, dispatch

    devices = list(topo.devices[:4])
    plan = Tuner().plan("summa", 4096, devices=devices, dtype="float32",
                        local_kernel="pallas", use_cache=False)
    plan = dataclasses.replace(plan, variant="2d")
    assert (plan.g, plan.c, plan.local_kernel) == (2, 1, "pallas")
    fn, mesh = dispatch.executor(plan, devices)
    assert mesh.devices.shape == (2, 2)
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.float32,
                             sharding=NamedSharding(mesh, P("row", "col")))
    assert "tpu_custom_call" in fn.lower(x, x).compile().as_text()
