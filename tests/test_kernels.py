"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (TilePlan, cholesky, cholesky_ref, flash_attention,
                           flash_attention_ref, matmul, matmul_ref, ssm_scan,
                           ssm_scan_ref, trsm, trsm_diag_inv_pallas,
                           trsm_diag_pallas, trsm_ref)
from repro.kernels.cholesky import ops as chol_ops
from repro.kernels.trsm import ops as trsm_ops

RNG = np.random.default_rng(42)


def _rel(got, ref):
    g = np.asarray(got, np.float32)
    r = np.asarray(ref, np.float32)
    return np.abs(g - r).max() / max(np.abs(r).max(), 1e-6)


class TestMatmul:
    @pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 256),
                                       (300, 700, 260), (512, 1024, 384),
                                       (64, 64, 64)])
    @pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, m, k, n, dt):
        a = jnp.asarray(RNG.standard_normal((m, k)), dt)
        b = jnp.asarray(RNG.standard_normal((k, n)), dt)
        tol = 2e-2 if dt == jnp.bfloat16 else 1e-5
        assert _rel(matmul(a, b), matmul_ref(a, b)) < tol

    def test_out_dtype(self):
        a = jnp.asarray(RNG.standard_normal((256, 256)), jnp.bfloat16)
        out = matmul(a, a, out_dtype=jnp.float32)
        assert out.dtype == jnp.float32


class TestTrsm:
    @pytest.mark.parametrize("n,m", [(256, 256), (512, 384), (768, 256),
                                     (64, 32)])
    @pytest.mark.parametrize("dt", [jnp.float32])
    def test_sweep(self, n, m, dt):
        u = jnp.asarray(np.triu(RNG.standard_normal((n, n)))
                        + 2 * np.sqrt(n) * np.eye(n), dt)
        b = jnp.asarray(RNG.standard_normal((m, n)), dt)
        assert _rel(trsm(u, b), trsm_ref(u, b)) < 1e-4

    def test_solves_the_system(self):
        n = 256
        u = jnp.asarray(np.triu(RNG.standard_normal((n, n))) + 40 * np.eye(n),
                        jnp.float32)
        b = jnp.asarray(RNG.standard_normal((n, n)), jnp.float32)
        x = trsm(u, b)
        assert _rel(x @ u, b) < 1e-4

    @pytest.mark.parametrize("nb,m", [(1, 200), (2, 384), (3, 200),
                                      (5, 72), (8, 520)])
    def test_recursive_blocking(self, nb, m):
        """nb diagonal blocks of 128, split in halves (odd counts send the
        remainder right); m need not be n or a multiple of 128."""
        n = nb * 128
        u = jnp.asarray(np.triu(RNG.standard_normal((n, n)))
                        + 2 * np.sqrt(n) * np.eye(n), jnp.float32)
        b = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
        x = trsm(u, b, tiles=TilePlan.make("trsm", block=128))
        assert x.shape == (m, n)
        assert _rel(x, trsm_ref(u, b)) < 1e-4

    def test_single_block_is_the_diagonal_kernel(self):
        """One block with fewer rows than twice its width (Cholesky's last
        panels) runs the diagonal kernel alone: bit for bit what the
        kernel returns."""
        n, m = 128, 128
        u = jnp.asarray(np.triu(RNG.standard_normal((n, n)))
                        + 2 * np.sqrt(n) * np.eye(n), jnp.float32)
        b = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
        got = trsm(u, b, tiles=TilePlan.make("trsm", block=128))
        want = jax.jit(functools.partial(trsm_diag_pallas,
                                         interpret=True))(u, b)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("n,m,block", [(128, 384, 128), (256, 1000, 128),
                                           (300, 700, 128), (640, 1500, 256),
                                           (100, 2000, 128)])
    def test_inverted_diagonal_blocks(self, n, m, block):
        """m >> block: each diagonal block is inverted once and applied on
        the MXU, within the substitution's tolerance; n and m need not be
        multiples of the block."""
        assert trsm_ops.diag_split(m, n, block)[1] == 0
        u = jnp.asarray(np.triu(RNG.standard_normal((n, n)))
                        + 2 * np.sqrt(n) * np.eye(n), jnp.float32)
        b = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
        x = trsm(u, b, tiles=TilePlan.make("trsm", block=block))
        assert x.shape == (m, n)
        assert _rel(x, trsm_ref(u, b)) < 1e-4

    @pytest.mark.parametrize("m,n,block,want", [
        (16384, 16384, 128, (128, 0)),   # the linalg cell's TRSM
        (256, 1024, 128, (8, 0)),        # m = 2b: inverted
        (129, 1024, 128, (8, 0)),        # rows are padded to 256 first
        (128, 1024, 128, (0, 8)),        # m = b: substituted
        (384, 512, 256, (0, 2)),
        (1000, 300, 512, (1, 0)),        # block capped at the edge, 384
        (600, 300, 512, (0, 1))])
    def test_diag_split_is_what_the_wrapper_does(self, monkeypatch, m, n,
                                                 block, want):
        """The rule switches at m = 2b on padded shapes, and the wrapper's
        trace calls the kernels it says: one batched inversion covering
        every block, or one substitution per block."""
        assert trsm_ops.diag_split(m, n, block) == want
        seen = [0, 0]

        def inv(u, *, block, **kw):
            seen[0] += u.shape[0] // block
            return trsm_diag_inv_pallas(u, block=block, **kw)

        def sub(u, b, **kw):
            seen[1] += 1
            return trsm_diag_pallas(u, b, **kw)

        monkeypatch.setattr(trsm_ops, "trsm_diag_inv_pallas", inv)
        monkeypatch.setattr(trsm_ops, "trsm_diag_pallas", sub)
        jax.eval_shape(functools.partial(trsm_ops.trsm.__wrapped__,
                                         block=block),
                       jax.ShapeDtypeStruct((n, n), jnp.float32),
                       jax.ShapeDtypeStruct((m, n), jnp.float32))
        assert tuple(seen) == want

    @pytest.mark.parametrize("n,block", [(512, 128), (1024, 512)])
    @pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
    def test_block_inverse_is_upper_triangular(self, n, block, dt):
        """Each diagonal block's inverse is exactly zero below its diagonal
        (block 512 is two row blocks, the second starting at column 256)
        and inverts its block."""
        u = np.triu(RNG.standard_normal((n, n))) + 2 * np.sqrt(n) * np.eye(n)
        inv = np.asarray(trsm_diag_inv_pallas(jnp.asarray(u, dt),
                                              block=block, interpret=True),
                         np.float32)
        assert inv.shape == (n, block)
        tol = 2e-2 if dt == jnp.bfloat16 else 1e-5
        for i in range(n // block):
            blk = inv[i * block:(i + 1) * block]
            assert np.all(np.tril(blk, -1) == 0)
            ui = np.asarray(jnp.asarray(u, dt), np.float32)[
                i * block:(i + 1) * block, i * block:(i + 1) * block]
            assert np.abs(blk @ ui - np.eye(block)).max() < tol


class TestCholesky:
    @pytest.mark.parametrize("n", [64, 256, 512, 768])
    def test_sweep(self, n):
        m = RNG.standard_normal((n, n))
        a = jnp.asarray(m @ m.T + n * np.eye(n), jnp.float32)
        l = cholesky(a)
        assert _rel(l, cholesky_ref(a)) < 1e-4
        assert _rel(l @ l.T, a) < 1e-4
        # strictly-lower triangular
        assert np.allclose(np.triu(np.asarray(l), 1), 0)

    @pytest.mark.parametrize("n,block,want", [(16384, 512, (30, 1)),
                                              (1024, 256, (2, 1)),
                                              (700, 128, (4, 1)),
                                              (512, 256, (0, 1))])
    def test_panels_by_the_trsm_rule(self, n, block, want):
        """Every panel solve but the short last ones inverts L_jj^T."""
        assert chol_ops.diag_split(n, block) == want

    @pytest.mark.parametrize("n,block", [(1024, 256), (700, 128)])
    def test_inverted_panels_keep_l_lower(self, n, block):
        """On the inverse path L is exactly zero above its diagonal."""
        assert chol_ops.diag_split(n, block)[0] > 0
        m = RNG.standard_normal((n, n))
        a = jnp.asarray(m @ m.T + n * np.eye(n), jnp.float32)
        l = cholesky(a, tiles=TilePlan.make("cholesky", block=block))
        assert np.all(np.triu(np.asarray(l), 1) == 0)
        assert _rel(l, cholesky_ref(a)) < 1e-4


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,kv,s,d,causal", [
        (2, 4, 2, 256, 64, True), (1, 8, 1, 384, 128, True),
        (2, 4, 4, 300, 64, False), (1, 2, 2, 64, 64, True),
        (1, 6, 3, 256, 96, True),
    ])
    def test_sweep(self, b, h, kv, s, d, causal):
        q = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, kv, s, d)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, kv, s, d)), jnp.float32)
        got = flash_attention(q, k, v, causal=causal)
        ref = flash_attention_ref(
            q.reshape(b * h, s, d), k.reshape(b * kv, s, d),
            v.reshape(b * kv, s, d), causal=causal).reshape(b, h, s, d)
        assert np.abs(np.asarray(got - ref)).max() < 2e-5

    def test_bf16(self):
        b, h, s, d = 1, 4, 256, 64
        q = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.bfloat16)
        k = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.bfloat16)
        v = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.bfloat16)
        got = flash_attention(q, k, v)
        ref = flash_attention_ref(q.reshape(h, s, d), k.reshape(h, s, d),
                                  v.reshape(h, s, d)).reshape(b, h, s, d)
        assert _rel(got, ref) < 3e-2

    def test_rows_sum_to_one_property(self):
        """output of attention over identical values = that value."""
        b, h, s, d = 1, 2, 256, 64
        q = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
        v = jnp.ones((b, h, s, d), jnp.float32) * 3.25
        got = flash_attention(q, k, v, causal=True)
        assert np.allclose(np.asarray(got), 3.25, atol=1e-4)


class TestSSMScan:
    @pytest.mark.parametrize("b,h,s,dk,dv", [
        (2, 2, 256, 64, 64), (1, 4, 300, 64, 128), (1, 1, 512, 128, 129),
        (1, 2, 64, 32, 32),
    ])
    def test_sweep(self, b, h, s, dk, dv):
        q = jnp.asarray(RNG.standard_normal((b, h, s, dk)) * 0.3, jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, h, s, dk)) * 0.3, jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, h, s, dv)), jnp.float32)
        la = jnp.asarray(-np.abs(RNG.standard_normal((b, h, s))) * 0.1,
                         jnp.float32)
        got = ssm_scan(q, k, v, la)
        ref = ssm_scan_ref(q.reshape(b * h, s, dk), k.reshape(b * h, s, dk),
                           v.reshape(b * h, s, dv),
                           la.reshape(b * h, s)).reshape(b, h, s, dv)
        assert _rel(got, ref) < 1e-4

    def test_no_decay_equals_cumulative_linear_attention(self):
        """log_a = 0 -> plain (unnormalized) linear attention prefix sums."""
        b, h, s, d = 1, 1, 256, 32
        q = jnp.asarray(RNG.standard_normal((b, h, s, d)) * 0.2, jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, h, s, d)) * 0.2, jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
        la = jnp.zeros((b, h, s), jnp.float32)
        got = np.asarray(ssm_scan(q, k, v, la))[0, 0]
        scores = np.tril(np.asarray(q)[0, 0] @ np.asarray(k)[0, 0].T)
        ref = scores @ np.asarray(v)[0, 0]
        assert np.abs(got - ref).max() / np.abs(ref).max() < 1e-4

    def test_strong_decay_kills_history(self):
        """log_a = -inf-ish -> y_t = (q_t . k_t) v_t only."""
        b, h, s, d = 1, 1, 128, 32
        q = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
        la = jnp.full((b, h, s), -60.0, jnp.float32)
        got = np.asarray(ssm_scan(q, k, v, la))[0, 0]
        diag = np.einsum("sd,sd->s", np.asarray(q)[0, 0], np.asarray(k)[0, 0])
        ref = diag[:, None] * np.asarray(v)[0, 0]
        assert np.abs(got - ref).max() / np.abs(ref).max() < 1e-4
