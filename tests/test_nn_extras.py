"""Long-tail nn layers/functionals (reference: python/paddle/nn full name
surface; rnnt_loss vs torchaudio, grid_sample vs torch).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from conftest import needs_reference
from paddle_tpu import nn
from paddle_tpu.nn import functional as F


@needs_reference
def test_full_nn_name_surface():
    import re
    for ref_path, mod in [
            ('/root/reference/python/paddle/nn/__init__.py', nn),
            ('/root/reference/python/paddle/nn/functional/__init__.py', F)]:
        ref = open(ref_path).read()
        names = {n for n in set(re.findall(r"'(\w+)'", ref))
                 if not n.startswith('_')}
        missing = sorted(n for n in names if not hasattr(mod, n))
        assert not missing, (ref_path, missing)


def test_max_unpool2d_roundtrip():
    x = paddle.to_tensor(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
    pooled, idx = F.max_pool2d(x, 2, stride=2, return_mask=True)
    un = F.max_unpool2d(pooled, idx, 2, stride=2)
    assert un.shape == [1, 1, 4, 4]
    out = un.numpy()[0, 0]
    # max values restored at their original positions, zeros elsewhere
    assert out[1, 1] == 5.0 and out[3, 3] == 15.0
    assert out[0, 0] == 0.0
    layer = nn.MaxUnPool2D(2, stride=2)
    np.testing.assert_allclose(layer(pooled, idx).numpy(), un.numpy())


def test_adaptive_and_fractional_pool3d():
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(1, 2, 8, 8, 8).astype(np.float32))
    out = F.adaptive_max_pool3d(x, 2)
    assert out.shape == [1, 2, 2, 2, 2]
    np.testing.assert_allclose(float(out.numpy().max()),
                               float(x.numpy().max()))
    f2 = F.fractional_max_pool2d(
        paddle.to_tensor(np.random.RandomState(1).randn(1, 1, 9, 9)
                         .astype(np.float32)), 4, random_u=0.3)
    assert f2.shape == [1, 1, 4, 4]
    f3 = nn.FractionalMaxPool3D(2, random_u=0.5)(x)
    assert f3.shape == [1, 2, 2, 2, 2]


def test_grid_sample_matches_torch():
    torch = pytest.importorskip("torch")
    rng = np.random.RandomState(2)
    x = rng.randn(2, 3, 5, 6).astype(np.float32)
    theta = np.tile(np.array([[[0.8, 0.1, 0.0], [-0.1, 0.9, 0.1]]],
                             np.float32), (2, 1, 1))
    grid_ours = F.affine_grid(paddle.to_tensor(theta), [2, 3, 5, 6]).numpy()
    grid_ref = torch.nn.functional.affine_grid(
        torch.tensor(theta), (2, 3, 5, 6), align_corners=True).numpy()
    np.testing.assert_allclose(grid_ours, grid_ref, rtol=1e-5, atol=1e-6)
    out_ours = F.grid_sample(paddle.to_tensor(x),
                             paddle.to_tensor(grid_ours)).numpy()
    out_ref = torch.nn.functional.grid_sample(
        torch.tensor(x), torch.tensor(grid_ref), mode="bilinear",
        padding_mode="zeros", align_corners=True).numpy()
    np.testing.assert_allclose(out_ours, out_ref, rtol=1e-4, atol=1e-5)


def test_rnnt_loss_matches_torchaudio():
    ta = pytest.importorskip("torchaudio")
    torch = pytest.importorskip("torch")
    rng = np.random.RandomState(3)
    b, t, u, v = 2, 6, 3, 5
    logits = rng.randn(b, t, u + 1, v).astype(np.float32)
    labels = rng.randint(1, v, (b, u)).astype(np.int32)
    t_lens = np.array([6, 5], np.int32)
    u_lens = np.array([3, 2], np.int32)
    ref = ta.functional.rnnt_loss(
        torch.tensor(logits), torch.tensor(labels),
        torch.tensor(t_lens), torch.tensor(u_lens), blank=0,
        reduction="none").numpy()
    ours = F.rnnt_loss(paddle.to_tensor(logits), paddle.to_tensor(labels),
                       paddle.to_tensor(t_lens), paddle.to_tensor(u_lens),
                       blank=0, reduction="none").numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-4)


def test_rnnt_loss_grad():
    rng = np.random.RandomState(4)
    logits = paddle.to_tensor(rng.randn(1, 4, 3, 5).astype(np.float32))
    logits.stop_gradient = False
    loss = F.rnnt_loss(logits, paddle.to_tensor(np.array([[1, 2]], np.int32)),
                       paddle.to_tensor(np.array([4], np.int32)),
                       paddle.to_tensor(np.array([2], np.int32)))
    loss.backward()
    assert logits.grad is not None
    assert np.isfinite(logits.grad.numpy()).all()


def test_losses_match_torch():
    torch = pytest.importorskip("torch")
    rng = np.random.RandomState(5)
    x = rng.randn(4, 6).astype(np.float32)
    y = (rng.rand(4, 6) > 0.5).astype(np.float32)
    ours = F.multi_label_soft_margin_loss(paddle.to_tensor(x),
                                          paddle.to_tensor(y))
    ref = torch.nn.functional.multilabel_soft_margin_loss(
        torch.tensor(x), torch.tensor(y))
    np.testing.assert_allclose(float(ours.numpy()), float(ref), rtol=1e-5)

    lab = rng.randint(0, 6, (4,))
    ours2 = F.multi_margin_loss(paddle.to_tensor(x),
                                paddle.to_tensor(lab.astype(np.int64)))
    ref2 = torch.nn.functional.multi_margin_loss(
        torch.tensor(x), torch.tensor(lab))
    np.testing.assert_allclose(float(ours2.numpy()), float(ref2), rtol=1e-5)

    sy = np.where(y > 0, 1.0, -1.0).astype(np.float32)
    ours3 = F.soft_margin_loss(paddle.to_tensor(x), paddle.to_tensor(sy))
    ref3 = torch.nn.functional.soft_margin_loss(torch.tensor(x),
                                                torch.tensor(sy))
    np.testing.assert_allclose(float(ours3.numpy()), float(ref3), rtol=1e-5)

    var = np.abs(rng.randn(4, 6)).astype(np.float32) + 0.1
    ours4 = F.gaussian_nll_loss(paddle.to_tensor(x), paddle.to_tensor(y),
                                paddle.to_tensor(var))
    ref4 = torch.nn.functional.gaussian_nll_loss(
        torch.tensor(x), torch.tensor(y), torch.tensor(var))
    np.testing.assert_allclose(float(ours4.numpy()), float(ref4),
                               rtol=1e-4, atol=1e-5)

    ours5 = F.poisson_nll_loss(paddle.to_tensor(x), paddle.to_tensor(y))
    ref5 = torch.nn.functional.poisson_nll_loss(torch.tensor(x),
                                                torch.tensor(y))
    np.testing.assert_allclose(float(ours5.numpy()), float(ref5),
                               rtol=1e-4)

    a, p, n = (rng.randn(3, 8).astype(np.float32) for _ in range(3))
    ours6 = F.triplet_margin_with_distance_loss(
        paddle.to_tensor(a), paddle.to_tensor(p), paddle.to_tensor(n))
    ref6 = torch.nn.functional.triplet_margin_loss(
        torch.tensor(a), torch.tensor(p), torch.tensor(n))
    np.testing.assert_allclose(float(ours6.numpy()), float(ref6),
                               rtol=1e-4)


def test_dice_and_pairwise():
    probs = paddle.to_tensor(np.array([[[0.9, 0.1], [0.2, 0.8]]],
                                      np.float32))
    lab = paddle.to_tensor(np.array([[[0], [1]]], np.int64))
    d = F.dice_loss(probs, lab)
    assert 0 <= float(d.numpy()) < 0.2
    x = paddle.to_tensor(np.array([[0., 0.], [1., 1.]], np.float32))
    y = paddle.to_tensor(np.array([[3., 4.], [1., 1.]], np.float32))
    pd = F.pairwise_distance(x, y).numpy()
    np.testing.assert_allclose(pd, [5.0, 0.0], atol=1e-4)


def test_hsigmoid_loss_decreases():
    rng = np.random.RandomState(6)
    layer = nn.HSigmoidLoss(8, 16)
    x = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
    lab = paddle.to_tensor(rng.randint(0, 16, (16,)).astype(np.int64))
    opt = paddle.optimizer.Adam(learning_rate=0.05,
                                parameters=layer.parameters())
    first = None
    for _ in range(30):
        loss = layer(x, lab)
        if first is None:
            first = float(loss.numpy())
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert float(loss.numpy()) < first * 0.7


def test_margin_cross_entropy_and_npair():
    rng = np.random.RandomState(7)
    emb = rng.randn(8, 16).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    w = rng.randn(16, 10).astype(np.float32)
    w /= np.linalg.norm(w, axis=0, keepdims=True)
    cos = paddle.to_tensor(emb @ w)
    lab = paddle.to_tensor(rng.randint(0, 10, (8,)).astype(np.int64))
    loss = F.margin_cross_entropy(cos, lab)
    assert np.isfinite(float(loss.numpy()))
    anchor = paddle.to_tensor(rng.randn(4, 8).astype(np.float32))
    pos = paddle.to_tensor(rng.randn(4, 8).astype(np.float32))
    labels = paddle.to_tensor(np.arange(4).astype(np.int64))
    nl = F.npair_loss(anchor, pos, labels)
    assert np.isfinite(float(nl.numpy()))


def test_class_center_sample():
    lab = paddle.to_tensor(np.array([3, 7, 3], np.int64))
    remapped, sampled = F.class_center_sample(lab, 20, 6)
    s = sampled.numpy()
    assert 3 in s and 7 in s and len(s) == 6
    r = remapped.numpy()
    assert r[0] == r[2] != r[1]


def test_zeropad2d_and_unflatten_layer():
    x = paddle.ones([1, 1, 2, 2])
    out = F.zeropad2d(x, [1, 1, 1, 1])
    assert out.shape == [1, 1, 4, 4]
    assert float(out.numpy()[0, 0, 0, 0]) == 0.0
    u = nn.Unflatten(1, [2, 2])(paddle.ones([3, 4]))
    assert u.shape == [3, 2, 2]


def test_gather_tree():
    ids = np.array([[[2, 5]], [[3, 6]], [[4, 7]]], np.int32)  # (T, B=1, W=2)
    parents = np.array([[[0, 0]], [[1, 0]], [[1, 0]]], np.int32)
    out = F.gather_tree(paddle.to_tensor(ids),
                        paddle.to_tensor(parents)).numpy()
    # beam 0 final: t2 chose parent 1 -> t1 beam1 (6), which chose parent 0
    np.testing.assert_array_equal(out[:, 0, 0], [2, 6, 4])


def test_inplace_activations():
    x = paddle.to_tensor(np.array([-2.0, 0.5], np.float32))
    F.tanh_(x)
    np.testing.assert_allclose(x.numpy(), np.tanh([-2.0, 0.5]), rtol=1e-6)
    y = paddle.to_tensor(np.array([-2.0, 0.5], np.float32))
    F.leaky_relu_(y)
    np.testing.assert_allclose(y.numpy(), [-0.02, 0.5], rtol=1e-5)


def test_rnnt_loss_matches_bruteforce():
    """Enumerate all monotonic alignments for a tiny lattice and compare
    -log sum exp of path scores to the scan DP."""
    import itertools
    rng = np.random.RandomState(8)
    t, u, v = 3, 2, 4
    logits = rng.randn(1, t, u + 1, v).astype(np.float32)
    labels = np.array([[1, 2]], np.int32)
    logp = np.log(np.exp(logits) / np.exp(logits).sum(-1, keepdims=True))

    # paths: sequences of (blank|emit) moves from (0,0) to (T-1,U) ending
    # with blank at (T-1, U). A path has T blanks and U emits; the last
    # move is the final blank consumed at t=T-1,u=U.
    total = []
    # choose positions of emits among the T+U moves, with the constraint
    # that the path stays in-grid; enumerate all interleavings
    for moves in itertools.permutations(["b"] * t + ["e"] * u):
        # dedupe permutations of identical items
        pass
    seen = set()
    scores = []
    for moves in set(itertools.permutations(["b"] * t + ["e"] * u)):
        ti, ui, s = 0, 0, 0.0
        ok = True
        for m in moves:
            if m == "b":
                s += logp[0, ti, ui, 0]
                ti += 1
            else:
                if ui >= u or ti >= t:
                    ok = False
                    break
                s += logp[0, ti, ui, labels[0, ui]]
                ui += 1
        # valid path: consumed all T time steps (last blank exits at T)
        if ok and ti == t and ui == u:
            scores.append(s)
    ref = -np.logaddexp.reduce(scores)
    ours = F.rnnt_loss(paddle.to_tensor(logits), paddle.to_tensor(labels),
                       paddle.to_tensor(np.array([t], np.int32)),
                       paddle.to_tensor(np.array([u], np.int32)),
                       fastemit_lambda=0.0, reduction="none").numpy()[0]
    np.testing.assert_allclose(ours, ref, rtol=1e-4)
    # FastEmit regularization actually changes the loss (was silently
    # dropped before)
    fe = F.rnnt_loss(paddle.to_tensor(logits), paddle.to_tensor(labels),
                     paddle.to_tensor(np.array([t], np.int32)),
                     paddle.to_tensor(np.array([u], np.int32)),
                     fastemit_lambda=0.1, reduction="none").numpy()[0]
    assert fe != ours


def test_hsigmoid_non_power_of_two_classes():
    rng = np.random.RandomState(9)
    layer = nn.HSigmoidLoss(4, 3)  # num_classes=3: classes have unequal
    x = paddle.to_tensor(rng.randn(6, 4).astype(np.float32))
    lab = paddle.to_tensor(np.array([0, 1, 2, 0, 1, 2], np.int64))
    loss = layer(x, lab)
    assert np.isfinite(float(loss.numpy()))
    opt = paddle.optimizer.Adam(learning_rate=0.1,
                                parameters=layer.parameters())
    first = float(loss.numpy())
    for _ in range(20):
        loss = layer(x, lab)
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert float(loss.numpy()) < first


def test_fractional_pool_stochastic_by_default():
    paddle.seed(123)
    x = paddle.to_tensor(
        np.random.RandomState(10).randn(1, 1, 9, 9).astype(np.float32))
    outs = {tuple(F.fractional_max_pool2d(x, 4).numpy().ravel())
            for _ in range(8)}
    assert len(outs) > 1  # regions resampled per call
    with pytest.raises(NotImplementedError):
        F.fractional_max_pool2d(x, 4, return_mask=True)


def test_grid_sample_reflection_matches_torch():
    torch = pytest.importorskip("torch")
    rng = np.random.RandomState(11)
    x = rng.randn(1, 1, 4, 4).astype(np.float32)
    # out-of-range grid exercises the padding mode
    grid = (rng.rand(1, 3, 3, 2).astype(np.float32) * 3 - 1.5)
    ours = F.grid_sample(paddle.to_tensor(x), paddle.to_tensor(grid),
                         padding_mode="reflection").numpy()
    ref = torch.nn.functional.grid_sample(
        torch.tensor(x), torch.tensor(grid), mode="bilinear",
        padding_mode="reflection", align_corners=True).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_pixel_shuffle_nhwc_matches_nchw():
    """NHWC channel ordering must match the reference kernels
    (pixel_shuffle_kernel_impl.h / pixel_unshuffle_kernel_impl.h /
    channel_shuffle_kernel_impl.h): cross-check every NHWC op against its
    NCHW counterpart through layout transposes, plus round-trips."""
    import paddle_tpu as paddle
    from paddle_tpu.nn import functional as F
    rng = np.random.RandomState(0)
    x_nchw = rng.randn(2, 8, 4, 6).astype(np.float32)   # c=8, r=2
    t = paddle.to_tensor

    def nchw2nhwc(a):
        return np.transpose(a, (0, 2, 3, 1))

    for op, arg in ((F.pixel_shuffle, 2), (F.pixel_unshuffle, 2),
                    (F.channel_shuffle, 4)):
        ref = np.asarray(op(t(x_nchw), arg).numpy())
        got = np.asarray(op(t(nchw2nhwc(x_nchw)), arg,
                            data_format="NHWC").numpy())
        np.testing.assert_allclose(got, nchw2nhwc(ref), rtol=0, atol=0)

    # round-trip in NHWC
    xh = t(nchw2nhwc(x_nchw))
    back = F.pixel_shuffle(F.pixel_unshuffle(xh, 2, data_format="NHWC"),
                           2, data_format="NHWC")
    np.testing.assert_allclose(np.asarray(back.numpy()),
                               nchw2nhwc(x_nchw), rtol=0, atol=0)
