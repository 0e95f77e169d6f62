"""Per-kernel shape/dtype sweeps: pallas_call (interpret) vs ref.py oracle
vs the numpy host codec (three-way agreement)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.coders import DiscreteCoder, quantize_freqs  # noqa: E402
from repro.core.vectorized import encode_batch  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402


def _coder(rng, n):
    w = 1.0 / np.arange(1, n + 1) ** rng.uniform(0.4, 1.8)
    return DiscreteCoder(quantize_freqs(w * 1e7))


class TestAliasDecodeKernel:
    @pytest.mark.parametrize("n_symbols", [1, 2, 37, 255, 1000])
    @pytest.mark.parametrize("n_codes", [17, 1024, 4097])
    def test_sweep(self, n_symbols, n_codes):
        rng = np.random.default_rng(n_symbols * 1000 + n_codes)
        dc = _coder(rng, n_symbols)
        table, m = ref.pack_tables(dc)
        codes = rng.integers(0, 65536, n_codes).astype(np.int32)
        sym_k, a_k, k_k = ops.alias_decode(jnp.asarray(codes), table, m)
        sym_r, a_r, k_r = ref.alias_decode_ref(jnp.asarray(codes), table, m)
        sym_c, a_c, k_c = dc.inv_translate_batch(codes)
        np.testing.assert_array_equal(np.asarray(sym_k), sym_c)
        np.testing.assert_array_equal(np.asarray(a_k), a_c)
        np.testing.assert_array_equal(np.asarray(k_k), k_c)
        np.testing.assert_array_equal(np.asarray(sym_r), sym_c)


class TestDelayedDecodeKernel:
    @pytest.mark.parametrize("n_slots,n_tuples", [(1, 64), (5, 300), (24, 130)])
    def test_sweep(self, n_slots, n_tuples):
        rng = np.random.default_rng(n_slots * 7 + n_tuples)
        coders = [_coder(rng, int(rng.integers(2, 400)))
                  for _ in range(n_slots)]
        syms = np.stack([rng.integers(0, c.tables.n_symbols, n_tuples)
                         for c in coders], axis=1)
        codes_csr, offsets = encode_batch(syms, coders)
        dense = ops.dense_codes(codes_csr.astype(np.int64), offsets, n_slots)
        tables, mbits = ops.pack_slot_tables(coders)
        out_k = np.asarray(ops.delayed_decode(jnp.asarray(dense), tables,
                                              mbits))
        out_r = np.asarray(ref.delayed_decode_ref(jnp.asarray(dense), tables,
                                                  mbits))
        np.testing.assert_array_equal(out_r, syms)
        np.testing.assert_array_equal(out_k, syms)

    def test_skewed_distributions_stress_virtual_bits(self):
        """Highly skewed slots mark nearly every interval (max virtual use)."""
        w = np.ones(3)
        w[0] = 1e6  # one dominant symbol -> k ~ 2**16 -> constant marking
        coders = [DiscreteCoder(quantize_freqs(w)) for _ in range(30)]
        syms = np.zeros((50, 30), np.int64)
        syms[:, ::7] = 1
        codes_csr, offsets = encode_batch(syms, coders)
        dense = ops.dense_codes(codes_csr.astype(np.int64), offsets, 30)
        tables, mbits = ops.pack_slot_tables(coders)
        out = np.asarray(ops.delayed_decode(jnp.asarray(dense), tables, mbits))
        np.testing.assert_array_equal(out, syms)


class TestDelayedDecodeTPUInterpret:
    """The compiled-kernel path (``interpret=False``) under Pallas's TPU
    interpreter, which models SMEM/VMEM and the kernel's scratch, on the
    slot plans of a fitted TPC-C database: bit-identical to numpy."""

    @pytest.fixture(scope="class")
    def tpcc_db(self):
        from repro.oltp import tpcc
        pop = tpcc.generate_tpcc(n_warehouses=1, districts_per_wh=10,
                                 customers_per_district=30, n_items=300,
                                 orders_per_district=30, seed=0)
        return tpcc.build_tpcc_database(population=pop)[0]

    @pytest.mark.parametrize("name", ["customer", "district", "item",
                                      "order_line", "orders", "stock"])
    def test_matches_numpy(self, tpcc_db, name):
        from jax.experimental.pallas import tpu as pltpu
        from repro.kernels import delayed_decode as dd

        t = tpcc_db[name].shards[0].table
        plan = t.codec.compile()
        rows = np.nonzero(t._fast[:t.n_blocks])[0][:256]
        assert rows.size
        codes = t.arena[:t.used].astype(np.int64)
        want = plan.decode_select(t.arena[:t.used], t.block_offsets, rows,
                                  backend="numpy")
        sel = np.concatenate([rows, np.full(256 - rows.size, rows[-1])])
        starts, ends = t.block_offsets[sel], t.block_offsets[sel + 1]
        runs = np.concatenate([codes[a:b] for a, b in zip(starts, ends)])
        dense = ops.dense_codes(
            runs, np.concatenate([[0], np.cumsum(ends - starts)]), plan.S)
        tables, m_bits = plan.pallas_tables()
        with pltpu.force_tpu_interpret_mode():
            got = dd._delayed_decode_jit(jnp.asarray(dense), tables, m_bits,
                                         False)
        np.testing.assert_array_equal(np.asarray(got)[:rows.size], want)


class TestKVAttentionKernel:
    @pytest.mark.parametrize("B,S,K,G,D", [
        (1, 256, 1, 1, 64), (2, 1024, 4, 3, 64), (2, 512, 8, 2, 128),
    ])
    @pytest.mark.parametrize("qdtype", [np.float32, jnp.bfloat16])
    def test_sweep(self, B, S, K, G, D, qdtype):
        rng = np.random.default_rng(B * S + K)
        H = K * G
        q = rng.normal(size=(B, H, D)).astype(np.float32)
        kf = rng.normal(size=(B, S, K, D)).astype(np.float32)
        vf = rng.normal(size=(B, S, K, D)).astype(np.float32)
        ks = np.abs(kf).max(-1) / 127.0 + 1e-8
        vs = np.abs(vf).max(-1) / 127.0 + 1e-8
        kq = np.clip(np.round(kf / ks[..., None]), -127, 127).astype(np.int8)
        vq = np.clip(np.round(vf / vs[..., None]), -127, 127).astype(np.int8)
        L = S - S // 3
        qj = jnp.asarray(q).astype(qdtype)
        out_k = np.asarray(ops.kv_attention_int8(
            qj, jnp.asarray(kq), jnp.asarray(ks), jnp.asarray(vq),
            jnp.asarray(vs), L, chunk=min(512, S)))
        out_r = np.asarray(ref.kv_attention_int8_ref(
            jnp.asarray(qj, jnp.float32), jnp.asarray(kq), jnp.asarray(vq),
            jnp.asarray(ks), jnp.asarray(vs), jnp.asarray(L)))
        tol = 5e-2 if qdtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(out_k, out_r, atol=tol, rtol=tol)

    def test_quantization_error_bounded(self):
        """int8 semantic quantization keeps attention output close to fp."""
        rng = np.random.default_rng(3)
        B, S, K, G, D = 1, 512, 2, 2, 64
        q = rng.normal(size=(B, K * G, D)).astype(np.float32)
        kf = rng.normal(size=(B, S, K, D)).astype(np.float32)
        vf = rng.normal(size=(B, S, K, D)).astype(np.float32)
        ks = np.abs(kf).max(-1) / 127.0 + 1e-8
        vs = np.abs(vf).max(-1) / 127.0 + 1e-8
        kq = np.clip(np.round(kf / ks[..., None]), -127, 127).astype(np.int8)
        vq = np.clip(np.round(vf / vs[..., None]), -127, 127).astype(np.int8)
        out_q = np.asarray(ops.kv_attention_int8(
            jnp.asarray(q), jnp.asarray(kq), jnp.asarray(ks),
            jnp.asarray(vq), jnp.asarray(vs), S))
        # fp reference attention (unquantized)
        import jax
        qf = q.reshape(B, K, G, D) * (D ** -0.5)
        s = np.einsum("bkgd,bskd->bkgs", qf, kf)
        p = np.asarray(jax.nn.softmax(jnp.asarray(s), axis=-1))
        out_f = np.einsum("bkgs,bskd->bkgd", p, vf).reshape(B, K * G, D)
        assert np.abs(out_q - out_f).max() < 0.05


class TestFlashPrefillKernel:
    """Fused prefill attention (§Perf cell-3 structural fix) vs the XLA
    chunked-attention reference across shapes, masks and dtypes."""

    @pytest.mark.parametrize("B,Sq,Sk,K,G,D,causal,win", [
        (2, 128, 128, 2, 3, 64, True, 0),
        (1, 200, 200, 4, 1, 32, True, 48),
        (2, 96, 160, 2, 2, 64, False, 0),
        (1, 64, 64, 1, 8, 128, True, 0),
    ])
    def test_matches_chunked_attention(self, B, Sq, Sk, K, G, D, causal, win):
        import jax
        from repro.kernels.flash_prefill import flash_prefill_attention
        from repro.models.layers import AttnSpec, chunked_attention
        rng = np.random.default_rng(B * Sq + Sk)
        H = K * G
        q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, Sk, K, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, Sk, K, D)), jnp.float32)
        out_k = flash_prefill_attention(q, k, v, causal=causal, window=win,
                                        q_block=64, kv_chunk=64)
        spec = AttnSpec(causal=causal, q_block=64, kv_chunk=64)
        out_r = chunked_attention(q, k, v, jnp.arange(Sq), spec,
                                  window=(win if win else None))
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16_io(self):
        from repro.kernels.flash_prefill import flash_prefill_attention
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, 64, 4, 32)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
        out = flash_prefill_attention(q, k, v, q_block=32, kv_chunk=32)
        assert out.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(out, np.float32)).all()
