"""The fused causal-attention kernel's mathematics (`ops/flash_attention.py`)
under the Pallas interpreter, reached through the private head-major core
(`_flash(..., True)`; the program has no switch for it), against full-softmax
attention in f32: output and the three gradients at the three shapes the
benchmark's cells send (keys wider than values with a key/value head a query
head; grouped heads; 64-wide grouped heads), f32 and bf16 operands; a score
scale of the caller's; documents packed into a sequence (segment ids) against
the plain body's document mask, with the table of first key blocks each query
block's walk starts from. And the entry's choice
(`models/nemotron_h.blockwise_causal_attention`): what a shape counts as, and
that a CPU lowering holds the plain body at every shape.

Batch 1 and few heads keep a case to seconds under the interpreter; the block
structure is the real one (blocks of 512 or 256 rows: S = 1024 has two
diagonal blocks and one below the diagonal).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from openembedding_tpu.models import nemotron_h as nh
from openembedding_tpu.ops import flash_attention as fa
from openembedding_tpu.utils import metrics

# (key width, value width, query heads, key/value heads)
LATENT = (192, 128, 4, 4)
GROUPED = (128, 128, 8, 2)
GROUPS_OF_16 = (128, 128, 32, 2)
NARROW = (64, 64, 4, 2)
SHAPES = {"latent": LATENT, "grouped": GROUPED, "narrow": NARROW}


def _operands(seq, d, dv, hq, hkv, dtype, seed=0):
    """Head-major q, k, v and a cotangent; scores of a few units, so the
    softmax is neither flat nor one-hot."""
    rng = np.random.default_rng(seed + seq + d + hq)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    return (draw(1, hq, seq, d), draw(1, hkv, seq, d), draw(1, hkv, seq, dv),
            draw(1, hq, seq, dv))


def _oracle(q, k, v, scale=None):
    """Full-softmax causal attention in f32 at `highest`, head-major."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    group, seq = q.shape[1] // k.shape[1], q.shape[2]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
    scale = 1 / np.sqrt(q.shape[-1]) if scale is None else scale
    s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s * scale, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


def _with_grads(core, q, k, v, w):
    """(output, dq, dk, dv) of sum(core(q, k, v) * w), all f32."""
    def loss(q, k, v):
        o = core(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o
    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return tuple(np.asarray(t, np.float32) for t in (o,) + grads)


def _plain(q, k, v, scale=None, n=None):
    """The plain blockwise body on head-major operands."""
    major = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return major(nh._blockwise_causal_attention(
        major(q), major(k), major(v), block=512, scale=scale, n=n))


def _worst(got, want):
    """Largest error of each of (o, dq, dk, dv) relative to that array's
    largest entry."""
    return [float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
            for g, w in zip(got, want)]


def _norm_gap(got, want):
    """Norm of the error of each of (o, dq, dk, dv) over the array's norm."""
    return [float(np.linalg.norm(g - w) / np.linalg.norm(w))
            for g, w in zip(got, want)]


@pytest.mark.parametrize("seq", [512, 1024])
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_kernel_equals_full_softmax_in_f32(seq, shape):
    q, k, v, w = _operands(seq, *shape, jnp.float32)
    want = _with_grads(_oracle, q, k, v, w)
    got = _with_grads(lambda *t: fa._flash(*t, True), q, k, v, w)
    assert got[0].shape == (1, shape[2], seq, shape[1])
    assert max(_worst(got, want)) < 2e-5, _worst(got, want)


@pytest.mark.parametrize("seq", [512, 1024])
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_kernel_in_bf16_is_as_close_as_the_plain_body(seq, shape):
    """bf16 operands: the kernel rounds P before it normalises and dS once;
    by the norm of its error against the f32 oracle it stays within a tenth
    of what the plain body itself reads on the same operands (2.0e-3-2.8e-3
    against 2.2e-3-3.3e-3 here: bf16's own rounding of the results)."""
    q, k, v, w = _operands(seq, *shape, jnp.bfloat16)
    want = _with_grads(_oracle, q, k, v, w)
    plain = _norm_gap(_with_grads(_plain, q, k, v, w), want)
    got = _norm_gap(_with_grads(lambda *t: fa._flash(*t, True), q, k, v, w),
                    want)
    assert all(g <= 1.1 * p for g, p in zip(got, plain)), (got, plain)
    assert max(got) < 4e-3


def test_groups_of_sixteen_sum_into_one_key_value_head():
    """NemotronH's 32 query heads over 2 key/value heads: dk and dv are sums
    over the 16 query heads of a group."""
    q, k, v, w = _operands(512, *GROUPS_OF_16, jnp.float32)
    want = _with_grads(_oracle, q, k, v, w)
    got = _with_grads(lambda *t: fa._flash(*t, True), q, k, v, w)
    assert got[2].shape == (1, 2, 512, 128)
    assert max(_worst(got, want)) < 2e-5


def test_first_block_is_fully_visible_and_the_diagonal_is_masked():
    """A row of the second block sees all of the first block and its own
    block up to itself: moving a key it cannot see changes nothing, moving
    one it can see does."""
    q, k, v, _ = _operands(1024, *LATENT, jnp.float32)
    core = jax.jit(lambda *t: fa._flash(*t, True))
    base = np.asarray(core(q, k, v))
    row = 700                                    # in the second block of 512
    for key, seen in ((0, True), (511, True), (700, True), (701, False),
                      (1023, False)):
        moved = np.asarray(core(q, k.at[:, :, key].add(1.0),
                                v.at[:, :, key].add(1.0)))
        assert bool(np.any(moved[:, :, row] != base[:, :, row])) is seen, key
    # the very first row attends to itself alone
    np.testing.assert_allclose(base[:, :, 0], np.asarray(v)[:, :, 0], atol=1e-6)


def test_a_given_scale_is_the_kernels_scale_forward_and_backward():
    """Granite's 1/64 in place of D^-1/2 = 1/8: the output and dq, dk, dv
    follow the oracle at that scale, and differ from the default's."""
    q, k, v, w = _operands(512, *NARROW, jnp.float32)
    want = _with_grads(lambda *t: _oracle(*t, scale=1 / 64), q, k, v, w)
    got = _with_grads(lambda *t: fa._flash(*t, True, 1 / 64), q, k, v, w)
    assert max(_worst(got, want)) < 2e-5, _worst(got, want)
    plain = _with_grads(lambda *t: _plain(*t, scale=1 / 64), q, k, v, w)
    assert max(_worst(got, plain)) < 2e-5
    default = _with_grads(lambda *t: fa._flash(*t, True), q, k, v, w)
    assert min(_worst(default, want)) > 1e-2


# where documents begin in a sequence of 2,048 (blocks of 512): mid-block
# starts, a document over two blocks, one starting ON a block edge, and the
# last block's first query in a document that began in block 2 while its
# later queries start three more (from 1,600: block 2 is visited and wholly
# masked for them), one of them a single position
STARTS = (0, 300, 1024, 1100, 1600, 1601, 1900)


def _packed(seq=2048, at=STARTS):
    starts = np.zeros((1, seq), np.int32)
    starts[0, list(at)] = 1
    n = nh.segment_ids(jnp.asarray(starts))
    began = fa.document_starts(n)
    return n, (fa.first_key_blocks(began, 512), began)


def test_first_key_blocks_and_their_share_by_hand():
    n, (first, began) = _packed()
    np.testing.assert_array_equal(began[0, [0, 299, 300, 1023, 1024, 1099, 1100,
                                            1599, 1600, 1601, 1602, 2047]],
                                  [0, 0, 300, 300, 1024, 1024, 1100, 1100,
                                   1600, 1601, 1601, 1900])
    # block 0 from 0; block 1's first query (512) is in the document of 300;
    # block 2 begins a document; block 3's first query (1536) is in the
    # document of 1100
    np.testing.assert_array_equal(first, [[0, 0, 2, 2]])
    assert float(fa.key_block_share(began, 512)) == pytest.approx(6 / 10)
    # one document: every causal block; at blocks of 256 the same starts skip more
    one = fa.document_starts(jnp.ones((2, 2048), jnp.int32))
    assert float(fa.key_block_share(one, 512)) == 1.0
    np.testing.assert_array_equal(fa.first_key_blocks(began, 256),
                                  [[0, 0, 1, 1, 4, 4, 4, 6]])
    assert float(fa.key_block_share(began, 256)) == pytest.approx(
        (1 + 2 + 2 + 3 + 1 + 2 + 3 + 2) / 36)


@pytest.mark.parametrize("shape,scale", [(NARROW, 1 / 64), (GROUPED, None)],
                         ids=["narrow_scaled", "grouped"])
def test_documents_in_the_kernel_equal_the_plain_bodys_mask(shape, scale):
    """Output and dq, dk, dv of the kernel given segment ids (its walk from
    the first key blocks, the document mask in every visited block) against
    the plain body given the same documents, f32: the rows of block 3 from
    1,600 on, for which block 2 is wholly masked, included."""
    q, k, v, w = _operands(2048, *shape, jnp.float32)
    n, docs = _packed()
    want = _with_grads(lambda *t: _plain(*t, scale=scale, n=n), q, k, v, w)
    got = _with_grads(lambda *t: fa._flash(*t, True, scale, docs), q, k, v, w)
    assert max(_worst(got, want)) < 2e-5, _worst(got, want)
    # the masked row is no accident of the comparison: one document a
    # sequence reads otherwise there
    whole = _with_grads(lambda *t: fa._flash(*t, True, scale), q, k, v, w)
    assert np.abs(whole[0][:, :, 1700] - got[0][:, :, 1700]).max() > 1e-2


def _major(x):
    return jnp.transpose(x, (0, 2, 1, 3))


def _diffusion_oracle(q, k, v, denoise):
    """Full softmax in f32 at `highest` under the block-diffusion pair rule
    (`nh.diffusion_pairs`) over the doubled sequence, head-major."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    group, seq = q.shape[1] // k.shape[1], q.shape[2]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    pos = np.arange(seq)
    seen = nh.diffusion_pairs(pos[:, None], pos[None, :], seq // 2, denoise)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
    s = jnp.where(seen, s / np.sqrt(q.shape[-1]), -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


# (2L, denoising block): one block of 128 a half, at the SDAR models' block
# of 4 and at 64; two blocks of 512 a half (the walk's loop over whole clean
# blocks, and a clean block at a noisy query block's position that masks the
# rows of its first denoising block whole)
@pytest.mark.parametrize("seq,denoise", [(256, 4), (256, 64), (2048, 4)])
def test_block_diffusion_kernel_equals_the_plain_masked_body(seq, denoise):
    """Output and dq, dk, dv of the kernel's block-diffusion walk against the
    plain body (`nh._diffusion_attention`), both against full softmax under
    the pair rule, f32."""
    q, k, v, w = _operands(seq, *GROUPED, jnp.float32)
    want = _with_grads(lambda *t: _diffusion_oracle(*t, denoise), q, k, v, w)
    plain = _with_grads(lambda *t: _major(nh._diffusion_attention(
        *map(_major, t), block=128, denoise=denoise)), q, k, v, w)
    got = _with_grads(lambda *t: fa._flash(*t, True, None, None, denoise),
                      q, k, v, w)
    assert max(_worst(plain, want)) < 2e-5, _worst(plain, want)
    assert max(_worst(got, plain)) < 2e-5, _worst(got, plain)
    # the rule is no causal mask over 2L: that one reads otherwise
    causal = _with_grads(_oracle, q, k, v, w)
    assert max(_worst(causal, want)) > 1e-2


def test_entry_counts_a_block_diffusion_call_by_its_shape():
    """A shape the kernel's block-diffusion walk takes counts as
    "block_diffusion"; one it refuses as "blockwise"; never as "fused"."""
    paths = ("fused", "blockwise", "block_diffusion")
    for width, took in ((128, "block_diffusion"), (8, "blockwise")):
        before = [_count(p) for p in paths]
        q = jnp.ones((1, 512, 2, width), jnp.float32)
        jax.jit(lambda q: nh.blockwise_causal_attention(
            q, q, q, block=8, denoise=4))(q)
        assert [_count(p) - b for p, b in zip(paths, before)] == \
            [int(p == took) for p in paths]


def test_cpu_lowering_of_a_block_diffusion_call_holds_the_plain_body():
    """Through the entry lowered for the CPU at a shape the kernel takes: no
    custom call, the plain body's result to the bit, forward and backward."""
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 512, h, 128)), jnp.float32)
               for h in (4, 2, 2))

    def grads(core):
        return jax.jit(jax.value_and_grad(
            lambda *t: jnp.sum(jnp.square(core(*t, block=128, denoise=4))),
            argnums=(0, 1, 2)))
    entry = grads(nh.blockwise_causal_attention)
    text = entry.lower(q, k, v).compile().as_text()
    assert "custom-call" not in text and "custom_call" not in text
    for got, want in zip(jax.tree_util.tree_leaves(entry(q, k, v)),
                         jax.tree_util.tree_leaves(
                             grads(nh._diffusion_attention)(q, k, v))):
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="no document starts"):
        nh.blockwise_causal_attention(q, k, v, denoise=4,
                                      starts=jnp.ones((1, 512), jnp.int32))


def test_kernel_behind_the_entry_layout_equals_the_plain_body():
    """`causal_attention` is (B, S, H, D)-major like the plain body; here
    with the interpreter in the kernel's place."""
    q, k, v, _ = _operands(512, *GROUPED, jnp.float32)
    major = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    want = nh._blockwise_causal_attention(major(q), major(k), major(v), block=128)
    got = major(fa._flash(q, k, v, True))
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("seq,d,dv,hq,hkv,block", [
    (512, 192, 128, 32, 32, 512), (4096, 128, 128, 32, 2, 512),
    (768, 128, 128, 4, 4, 256), (384, 128, 256, 4, 1, 128),
    (21, 128, 128, 4, 4, None), (512, 64, 64, 4, 4, 512),
    (512, 128, 64, 4, 4, None), (512, 200, 128, 4, 4, None),
    (512, 128, 128, 6, 4, None), (1 << 16, 128, 128, 4, 4, None),
    (8192, 64, 64, 32, 8, 512), (8192, 128, 128, 8, 1, 512),
    (512, 64, 128, 4, 4, None), (512, 32, 32, 4, 4, None),
    (1 << 14, 64, 64, 4, 4, None), (768, 64, 64, 6, 3, 256)])
def test_tiling_takes_what_it_can_tile(seq, d, dv, hq, hkv, block):
    assert fa.tiling(seq, d, dv, hq, hkv) == block


def _count(path):
    return metrics.report().get('attn.cores{path="%s"}' % path, 0.0)


@pytest.mark.parametrize("seq,width,path", [(21, 128, "blockwise"),
                                            (512, 128, "fused"),
                                            (512, 8, "blockwise")])
def test_entry_counts_a_call_site_by_its_shape(seq, width, path):
    """Counted once a traced call site; on the CPU both count the plain
    body's run (the counter speaks for the shape, not the platform)."""
    other = "fused" if path == "blockwise" else "blockwise"
    before = _count(path), _count(other)
    q = jnp.ones((1, seq, 2, width), jnp.float32)
    f = jax.jit(lambda q: nh.blockwise_causal_attention(q, q, q, block=8))
    f(q), f(q)
    assert (_count(path), _count(other)) == (before[0] + 1, before[1])


def test_entry_counts_a_packed_scaled_call_as_fused_and_as_a_reset():
    """Starts and a scale no longer send a call the kernel's tiling takes to
    the plain body's count; the call is still a reset site."""
    resets = lambda: metrics.report().get('pack.resets{site="attn"}', 0.0)
    before = _count("fused"), _count("blockwise"), resets()
    q = jnp.ones((1, 512, 2, 64), jnp.float32)
    starts = jnp.zeros((1, 512), jnp.int32).at[0, jnp.array([0, 200])].set(1)
    jax.jit(lambda q, st: nh.blockwise_causal_attention(
        q, q, q, block=8, scale=1 / 64, starts=st))(q, starts)
    assert (_count("fused"), _count("blockwise"), resets()) == \
        (before[0] + 1, before[1], before[2] + 1)


def test_cpu_lowering_of_a_packed_scaled_call_holds_the_plain_body():
    """The same on the CPU with starts and a scale: no custom call, the plain
    body's result with the documents' mask to the bit, forward and
    backward."""
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 512, h, 64)), jnp.float32)
               for h in (4, 2, 2))
    starts = np.zeros((1, 512), np.int32)
    starts[0, [0, 130, 131, 400]] = 1

    def grads(core):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v, st: jnp.sum(jnp.square(core(q, k, v, st))),
            argnums=(0, 1, 2)))
    entry = grads(lambda q, k, v, st: nh.blockwise_causal_attention(
        q, k, v, block=128, scale=1 / 64, starts=st))
    text = entry.lower(q, k, v, starts).compile().as_text()
    assert "custom-call" not in text and "custom_call" not in text
    plain = grads(lambda q, k, v, st: nh._blockwise_causal_attention(
        q, k, v, block=128, scale=1 / 64, n=nh.segment_ids(st)))
    for got, want in zip(jax.tree_util.tree_leaves(entry(q, k, v, starts)),
                         jax.tree_util.tree_leaves(plain(q, k, v, starts))):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seq,d,dv,hq,hkv", [(512, 192, 128, 2, 2),
                                             (512, 128, 128, 4, 2),
                                             (21, 128, 128, 2, 2),
                                             (32, 8, 8, 4, 2)])
def test_cpu_lowering_holds_the_plain_body_at_every_shape(seq, d, dv, hq, hkv):
    """Forward and backward through the entry lowered for the CPU: no custom
    call, the plain body's result to the bit."""
    rng = np.random.default_rng(seq)
    q, k, v = (jnp.asarray(rng.normal(size=(1, seq, h, w)), jnp.float32)
               for h, w in ((hq, d), (hkv, d), (hkv, dv)))

    def grads(core):
        return jax.jit(jax.value_and_grad(
            lambda *t: jnp.sum(jnp.square(core(*t, block=128))),
            argnums=(0, 1, 2)))
    entry = grads(nh.blockwise_causal_attention)
    text = entry.lower(q, k, v).compile().as_text()
    assert "custom-call" not in text and "custom_call" not in text
    for got, want in zip(jax.tree_util.tree_leaves(entry(q, k, v)),
                         jax.tree_util.tree_leaves(
                             grads(nh._blockwise_causal_attention)(q, k, v))):
        np.testing.assert_array_equal(got, want)
