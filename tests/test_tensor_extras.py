"""Long-tail tensor ops + generated inplace variants (reference:
python/paddle/tensor/__init__.py full name surface).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from conftest import needs_reference


@needs_reference
def test_full_reference_name_surface():
    import re
    ref = open('/root/reference/python/paddle/tensor/__init__.py').read()
    names = (set(re.findall(r"from \.\w+ import (\w+)", ref))
             | set(re.findall(r"'(\w+)'", ref)))
    names = {n for n in names
             if n.islower() and not n.startswith('_') and len(n) > 2}
    missing = sorted(n for n in names if not hasattr(paddle, n))
    assert not missing, missing


def test_take_and_modes():
    x = paddle.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
    np.testing.assert_allclose(
        paddle.take(x, paddle.to_tensor([0, 4, 5])).numpy(), [0, 4, 5])
    np.testing.assert_allclose(
        paddle.take(x, paddle.to_tensor([7]), mode="wrap").numpy(), [1])
    np.testing.assert_allclose(
        paddle.take(x, paddle.to_tensor([7]), mode="clip").numpy(), [5])


def test_add_n_cdist():
    a = paddle.ones([2, 2])
    out = paddle.add_n([a, a, a])
    np.testing.assert_allclose(out.numpy(), 3 * np.ones((2, 2)))
    x = paddle.to_tensor(np.array([[0., 0.], [1., 0.]], np.float32))
    y = paddle.to_tensor(np.array([[0., 1.]], np.float32))
    d = paddle.cdist(x, y).numpy()
    np.testing.assert_allclose(d, [[1.0], [np.sqrt(2)]], rtol=1e-5)


def test_diag_embed_and_scatters():
    v = paddle.to_tensor(np.array([1., 2., 3.], np.float32))
    m = paddle.diag_embed(v).numpy()
    np.testing.assert_allclose(m, np.diag([1., 2., 3.]))
    x = paddle.zeros([3, 3])
    out = paddle.diagonal_scatter(x, v).numpy()
    np.testing.assert_allclose(out, np.diag([1., 2., 3.]))
    out2 = paddle.select_scatter(paddle.zeros([2, 3]),
                                 paddle.to_tensor(np.array([9., 9., 9.],
                                                           np.float32)),
                                 0, 1).numpy()
    np.testing.assert_allclose(out2[1], [9., 9., 9.])
    out3 = paddle.slice_scatter(
        paddle.zeros([4]), paddle.to_tensor(np.array([5., 5.], np.float32)),
        axes=[0], starts=[1], ends=[3], strides=[1]).numpy()
    np.testing.assert_allclose(out3, [0., 5., 5., 0.])


def test_frexp_ldexp_roundtrip():
    x = paddle.to_tensor(np.array([1.5, -6.0, 0.25], np.float32))
    m, e = paddle.frexp(x)
    back = paddle.ldexp(m, e)
    np.testing.assert_allclose(back.numpy(), x.numpy(), rtol=1e-6)


def test_special_functions():
    import scipy.special as sp
    a = np.array([1.0, 2.5], np.float32)
    x = np.array([0.5, 2.0], np.float32)
    np.testing.assert_allclose(
        paddle.gammainc(paddle.to_tensor(a), paddle.to_tensor(x)).numpy(),
        sp.gammainc(a, x), rtol=1e-5)
    np.testing.assert_allclose(
        paddle.multigammaln(paddle.to_tensor(np.array([3.0], np.float32)),
                            2).numpy(),
        sp.multigammaln(3.0, 2), rtol=1e-5)
    assert paddle.signbit(paddle.to_tensor(
        np.array([-1.0, 1.0], np.float32))).numpy().tolist() == [True, False]


def test_multiplex_renorm_reverse():
    a = np.array([[1., 2.], [3., 4.]], np.float32)
    b = np.array([[5., 6.], [7., 8.]], np.float32)
    idx = np.array([[1], [0]], np.int32)
    out = paddle.multiplex([paddle.to_tensor(a), paddle.to_tensor(b)],
                           paddle.to_tensor(idx)).numpy()
    np.testing.assert_allclose(out, [[5., 6.], [3., 4.]])
    x = paddle.to_tensor(np.array([[3., 4.], [0.3, 0.4]], np.float32))
    r = paddle.renorm(x, 2.0, 0, 1.0).numpy()
    np.testing.assert_allclose(np.linalg.norm(r[0]), 1.0, rtol=1e-4)
    np.testing.assert_allclose(r[1], [0.3, 0.4], rtol=1e-5)  # under limit
    np.testing.assert_allclose(
        paddle.reverse(paddle.to_tensor(np.arange(3)), [0]).numpy(),
        [2, 1, 0])


def test_trapezoid():
    y = paddle.to_tensor(np.array([1., 2., 3.], np.float32))
    np.testing.assert_allclose(float(paddle.trapezoid(y).numpy()), 4.0)
    c = paddle.cumulative_trapezoid(y).numpy()
    np.testing.assert_allclose(c, [1.5, 4.0])


def test_unflatten_unstack_vander():
    x = paddle.to_tensor(np.arange(12, dtype=np.float32))
    u = paddle.unflatten(x, 0, [3, 4])
    assert u.shape == [3, 4]
    parts = paddle.unstack(u, axis=0)
    assert len(parts) == 3 and parts[0].shape == [4]
    v = paddle.vander(paddle.to_tensor(np.array([1., 2., 3.], np.float32)))
    np.testing.assert_allclose(v.numpy(), np.vander([1., 2., 3.]))


def test_top_p_sampling():
    paddle.seed(0)
    logits = np.full((2, 8), -1e9, np.float32)
    logits[0, 3] = 10.0  # all mass on one token
    logits[1, 5] = 10.0
    scores, ids = paddle.top_p_sampling(
        paddle.to_tensor(logits), paddle.to_tensor(
            np.array([0.9, 0.9], np.float32)))
    assert ids.numpy().ravel().tolist() == [3, 5]


def test_index_fill_put_masked_scatter():
    x = paddle.zeros([3, 3])
    out = paddle.index_fill(x, paddle.to_tensor(np.array([0, 2], np.int32)),
                            0, 7.0).numpy()
    np.testing.assert_allclose(out[0], [7., 7., 7.])
    np.testing.assert_allclose(out[1], [0., 0., 0.])

    out2 = paddle.index_put(
        paddle.zeros([2, 2]),
        (paddle.to_tensor(np.array([0, 1], np.int32)),
         paddle.to_tensor(np.array([1, 0], np.int32))),
        paddle.to_tensor(np.array([5., 6.], np.float32))).numpy()
    np.testing.assert_allclose(out2, [[0., 5.], [6., 0.]])

    mask = np.array([[True, False], [False, True]])
    vals = paddle.to_tensor(np.array([9., 8.], np.float32))
    out3 = paddle.masked_scatter(paddle.zeros([2, 2]),
                                 paddle.to_tensor(mask), vals).numpy()
    np.testing.assert_allclose(out3, [[9., 0.], [0., 8.]])


def test_generated_inplace_variants():
    x = paddle.to_tensor(np.array([1.0, 4.0], np.float32))
    x.sqrt_()
    np.testing.assert_allclose(x.numpy(), [1.0, 2.0])
    x.add_(paddle.to_tensor(np.array([1.0, 1.0], np.float32)))
    np.testing.assert_allclose(x.numpy(), [2.0, 3.0])
    # module-level free functions too
    paddle.log_(x)
    np.testing.assert_allclose(x.numpy(), np.log([2.0, 3.0]), rtol=1e-6)
    y = paddle.to_tensor(np.array([-1.0, 1.0], np.float32))
    y.abs_()
    np.testing.assert_allclose(y.numpy(), [1.0, 1.0])
    # version counter bumps for autograd safety
    v0 = y._version
    y.neg_()
    assert y._version > v0


def test_inplace_random_fills():
    paddle.seed(1)
    x = paddle.zeros([1000])
    x.cauchy_(loc=0.0, scale=1.0)
    med = np.median(np.abs(x.numpy()))
    assert 0.5 < med < 2.0  # |cauchy| median == scale
    x.geometric_(0.5)
    assert (x.numpy() >= 1).all()


def test_shape_and_printoptions():
    x = paddle.ones([2, 5])
    np.testing.assert_array_equal(paddle.shape(x).numpy(), [2, 5])
    paddle.set_printoptions(precision=4)
