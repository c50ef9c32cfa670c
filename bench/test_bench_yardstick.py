"""The yardstick: counts worked out by hand at smoke widths, blind to the
program's stored format, and every share it gives is at most 100%."""
import jax
import numpy as np
import pytest

from bench import cells, model, peaks, trace, yardstick
from bench.metrics import slide_gemm_roofline, step_mfu

V5E = peaks.for_kind("TPU v5 lite")


def smoke(recipe="none"):
    return dict(cells.load_config("danube4b-68-bf16"), hidden_size=64,
                intermediate_size=96, num_hidden_layers=1,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                vocab_size=128, sparsity={"pattern": [6, 8],
                                          "recipe": recipe})


def test_decode_step_by_hand():
    # linears of one layer, out x in: wq 64x64, wk/wv 32x64, wo 64x64,
    # gate/up 96x64, down 64x96 = 30720 weights; head 128x64 = 8192
    w = yardstick.decode_work(smoke(), [10, 20])
    nnz = 0.75 * (30720 + 8192)
    assert w.gemm_ops == 2 * nnz * 2
    per_weight = 6 * 2 / 8 + 5 / 64          # bf16 values + 5-bit codes
    act = 2 * 2 * (128 + 96 + 96 + 128 + 160 + 160 + 160)   # 2 rows
    head_act = 2 * 2 * (64 + 128)
    assert w.gemm_bytes == pytest.approx(
        (30720 + 8192) * per_weight + act + head_act)
    # keys seen: 11 and 21; K/V of 16-wide heads, 2 heads, bf16
    assert w.attn_ops == 4 * 64 * (11 + 21)
    assert w.attn_bytes == 2 * 2 * 16 * 2 * (10 + 20 + 2) + 2 * 2 * 64 * 2


def test_prefill_chunk_by_hand_int8():
    w = yardstick.prefill_work(smoke("int8"), 4, 3)
    # 3 rows through the layer, 1 row (the last position) through the head
    assert w.gemm_ops == 2 * 0.75 * (30720 * 3 + 8192)
    per_weight = 6 / 8 + 5 / 64               # int8 values + 5-bit codes
    scales = 4 * (64 + 32 + 32 + 64 + 96 + 96 + 64 + 128)
    act = 3 * (1 * (64 * 5 + 64 + 96) + 2 * (64 + 32 + 32 + 64 + 96 + 96 + 64))
    head_act = 1 * 64 + 2 * 128
    assert w.gemm_bytes == pytest.approx(
        (30720 + 8192) * per_weight + scales + act + head_act)
    assert w.attn_ops == 4 * 64 * (5 + 6 + 7)
    # a window shorter than the context caps the keys
    w2 = yardstick.prefill_work(dict(smoke(), sliding_window=5), 4, 3)
    assert w2.attn_ops == 4 * 64 * (5 + 5 + 5)


def _stored_bytes(cfg):
    from repro.models import model as M
    from repro.runtime import serve_loop

    return jax.eval_shape(lambda k: serve_loop.pack_params(M.init(cfg, k), cfg),
                          jax.random.PRNGKey(0))


def _nbytes(tree):
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("recipe", ["none", "int8"])
def test_blind_to_the_stored_format(recipe):
    """Doubling the index bytes of the packed tree changes what the program
    stores and not what the yardstick counts, which stays below both."""
    c = smoke(recipe)
    packed = _stored_bytes(model.program_config(c))
    doubled = jax.tree_util.tree_map_with_path(
        lambda p, s: jax.ShapeDtypeStruct(
            (2,) + s.shape if "indices" in jax.tree_util.keystr(p)
            else s.shape, s.dtype), packed)
    before = yardstick.decode_work(c, [7] * 4)
    assert _nbytes(doubled) > _nbytes(packed)
    after = yardstick.decode_work(c, [7] * 4)
    assert after == before
    weights = sum(yardstick.weight_bytes(c, k, m) * n
                  for k, m, n in yardstick.linears(c)) + \
        yardstick.weight_bytes(c, c["hidden_size"], c["vocab_size"])
    layers = _nbytes(packed) - _nbytes(packed["embed"])
    assert weights < layers < _nbytes(doubled)


@pytest.mark.parametrize("config", ["danube4b-68-bf16", "phi3m-68-int8"])
def test_shares_at_most_100_on_a_synthetic_step(config):
    """A decode step whose GEMM kernels stream the stored weights at the
    peak bandwidth, and nothing else, is the fastest the program could
    be: both shares stay under 100%."""
    c = cells.load_config(config)
    packed = _stored_bytes(model.program_config(c))
    stored = _nbytes(packed) - _nbytes(packed["embed"])
    w = yardstick.decode_work(c, [700] * 16)
    kernel_ns = int(stored / V5E.hbm_bytes_s * 1e9)
    step_ns = kernel_ns + int(w.attn_bytes / V5E.hbm_bytes_s * 1e9)
    summary = trace.Summary(
        (0, step_ns), [trace.Op(trace.GEMM_KERNEL, 0, kernel_ns)], [],
        [("bench.step", 0, step_ns, {"step": 0})])

    class Run:
        steps_work = {0: w}
        int8 = c["sparsity"]["recipe"] == "int8"

    Run.peaks = V5E
    gemm = slide_gemm_roofline.read(Run, summary)
    mfu = step_mfu.read(Run, summary)
    assert 0 < gemm <= 100 and 0 < mfu <= 100


def test_peaks_refuse_an_unknown_device():
    assert peaks.for_kind("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_kind("TPU v4")
