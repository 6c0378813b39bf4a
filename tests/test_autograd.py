"""Autograd engine tests: analytic + numeric gradient checks (the
reference OpTest grad-check methodology — SURVEY.md §4)."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.autograd import PyLayer


def t(arr, sg=False):
    return P.to_tensor(np.asarray(arr, dtype=np.float32), stop_gradient=sg)


def numeric_grad(f, x, eps=1e-3):
    """Central-difference gradient of scalar f at numpy point x."""
    g = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    while not it.finished:
        i = it.multi_index
        xp = x.copy()
        xp[i] += eps
        xm = x.copy()
        xm[i] -= eps
        g[i] = (f(xp) - f(xm)) / (2 * eps)
        it.iternext()
    return g


class TestBasicBackward:
    def test_simple_chain(self):
        x = t([2.0])
        y = x * x + 3.0 * x
        y.backward()
        assert np.allclose(x.grad.numpy(), [7.0])

    def test_grad_accumulation(self):
        x = t([1.0, 2.0])
        (x * 2).sum().backward()
        (x * 3).sum().backward()
        assert np.allclose(x.grad.numpy(), [5.0, 5.0])
        x.clear_grad()
        assert x.grad is None

    def test_broadcast_grad(self):
        x = t(np.ones((3, 4)))
        b = t(np.ones((4,)))
        (x * b).sum().backward()
        assert np.allclose(b.grad.numpy(), [3.0] * 4)
        assert np.allclose(x.grad.numpy(), np.ones((3, 4)))

    def test_matmul_grad_numeric(self):
        a = np.random.randn(3, 4).astype(np.float32)
        b = np.random.randn(4, 2).astype(np.float32)
        ta, tb = t(a), t(b)
        loss = P.matmul(ta, tb).sum()
        loss.backward()
        ga = numeric_grad(lambda x: (x @ b).sum(), a)
        gb = numeric_grad(lambda x: (a @ x).sum(), b)
        assert np.allclose(ta.grad.numpy(), ga, atol=1e-2)
        assert np.allclose(tb.grad.numpy(), gb, atol=1e-2)

    def test_nonlinear_grads_numeric(self):
        x0 = (np.random.rand(5).astype(np.float32) + 0.5)
        for fwd, np_fwd in [
            (lambda v: P.exp(v).sum(), lambda v: np.exp(v).sum()),
            (lambda v: P.log(v).sum(), lambda v: np.log(v).sum()),
            (lambda v: P.tanh(v).sum(), lambda v: np.tanh(v).sum()),
            (lambda v: (v ** 3).sum(), lambda v: (v ** 3).sum()),
        ]:
            x = t(x0.copy())
            fwd(x).backward()
            g = numeric_grad(np_fwd, x0)
            assert np.allclose(x.grad.numpy(), g, atol=1e-2)

    def test_multi_output_op_grad(self):
        x0 = np.random.randn(4, 4).astype(np.float32)
        x = t(x0)
        vals, idx = P.topk(x, 2, axis=1)
        vals.sum().backward()
        # grad is 1 at top-2 positions
        ref = np.zeros_like(x0)
        top2 = np.argsort(-x0, 1)[:, :2]
        for r in range(4):
            ref[r, top2[r]] = 1
        assert np.allclose(x.grad.numpy(), ref)

    def test_stop_gradient_blocks(self):
        x = t([1.0])
        y = t([2.0], sg=True)
        (x * y).backward()
        assert np.allclose(x.grad.numpy(), [2.0])
        assert y.grad is None

    def test_detach(self):
        x = t([3.0])
        d = x.detach()
        assert d.stop_gradient
        y = x * x
        z = y.detach() * x
        z.backward()
        assert np.allclose(x.grad.numpy(), [9.0])  # only through z's x

    def test_retain_graph(self):
        x = t([2.0])
        y = x * x
        y.backward(retain_graph=True)
        y.backward()
        assert np.allclose(x.grad.numpy(), [8.0])

    def test_double_backward_raises_without_retain(self):
        x = t([2.0])
        y = x * x
        y.backward()
        with pytest.raises(RuntimeError, match="second time"):
            y.backward()

    def test_getitem_grad(self):
        x = t(np.arange(12, dtype=np.float32).reshape(3, 4))
        x[1].sum().backward()
        ref = np.zeros((3, 4), np.float32)
        ref[1] = 1
        assert np.allclose(x.grad.numpy(), ref)

    def test_concat_split_grad(self):
        a, b = t(np.ones(3)), t(np.ones(3))
        c = P.concat([a, b])
        (c * P.to_tensor(np.arange(6, dtype=np.float32))).sum().backward()
        assert np.allclose(a.grad.numpy(), [0, 1, 2])
        assert np.allclose(b.grad.numpy(), [3, 4, 5])


class TestGradAPI:
    def test_paddle_grad(self):
        x = t([3.0])
        y = x * x
        (gx,) = P.grad(y, x)
        assert np.allclose(gx.numpy(), [6.0])
        assert x.grad is None  # .grad untouched

    def test_allow_unused(self):
        x, z = t([1.0]), t([1.0])
        y = x * 2
        with pytest.raises(RuntimeError):
            P.grad(y, [z])
        gx, gz = P.grad(x * 2, [x, z], allow_unused=True)
        assert gz is None

    def test_no_grad_context(self):
        x = t([1.0])
        with P.no_grad():
            y = x * x
        assert y.stop_gradient
        assert y._node is None


class TestHooks:
    def test_tensor_hook(self):
        x = t([1.0])
        x.register_hook(lambda g: g * 2)
        (x * 3).backward()
        assert np.allclose(x.grad.numpy(), [6.0])


class TestPyLayer:
    def test_custom_layer(self):
        class Cube(PyLayer):
            @staticmethod
            def forward(ctx, x):
                ctx.save_for_backward(x)
                return x * x * x

            @staticmethod
            def backward(ctx, grad):
                (x,) = ctx.saved_tensor
                return grad * 3 * x * x

        x = t([2.0])
        y = Cube.apply(x)
        assert np.allclose(y.numpy(), [8.0])
        y.backward()
        assert np.allclose(x.grad.numpy(), [12.0])


class TestHigherOrder:
    """create_graph double backward vs jax.grad∘jax.grad oracles
    (VERDICT r1 item 7)."""

    def test_grad_of_grad_scalar(self):
        import jax
        import jax.numpy as jnp

        def f(x):
            return jnp.sum(x ** 3 + 2.0 * x)

        xv = np.array([1.5, -2.0, 0.5], dtype=np.float32)
        x = t(xv)
        y = (x ** 3 + 2.0 * x).sum()
        (g,) = P.grad([y], [x], create_graph=True)
        assert not g.stop_gradient
        g2 = P.grad([g.sum()], [x])[0]
        oracle = jax.grad(lambda a: jnp.sum(jax.grad(f)(a)))(jnp.asarray(xv))
        assert np.allclose(g2.numpy(), np.asarray(oracle), atol=1e-5)

    def test_grad_of_grad_through_matmul(self):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        av = rng.standard_normal((3, 4)).astype(np.float32)
        bv = rng.standard_normal((4, 2)).astype(np.float32)

        def f(a, b):
            return jnp.sum(jnp.tanh(a @ b) ** 2)

        a, b = t(av), t(bv)
        y = (P.tanh(P.matmul(a, b)) ** 2).sum()
        (ga,) = P.grad([y], [a], create_graph=True)
        gg = P.grad([(ga * ga).sum()], [b])[0]
        oracle = jax.grad(
            lambda a_, b_: jnp.sum(jax.grad(f, argnums=0)(a_, b_) ** 2),
            argnums=1)(jnp.asarray(av), jnp.asarray(bv))
        assert np.allclose(gg.numpy(), np.asarray(oracle), atol=1e-4)

    def test_backward_after_create_graph_grad(self):
        """x.grad accumulation through a second .backward() on a
        create_graph first-order grad."""
        x = t([2.0])
        y = (x ** 4).sum()
        (g,) = P.grad([y], [x], create_graph=True)   # 4x^3 = 32
        g.sum().backward()                           # d/dx 4x^3 = 12x^2
        assert np.allclose(x.grad.numpy(), [48.0])

    def test_jacobian(self):
        from paddle_tpu.autograd import jacobian
        xv = np.array([1.0, 2.0, 3.0], dtype=np.float32)
        x = t(xv)
        y = x ** 2
        J = jacobian(y, x)
        assert list(J.shape) == [3, 3]
        assert np.allclose(J.numpy(), np.diag(2 * xv), atol=1e-5)

    def test_hessian(self):
        from paddle_tpu.autograd import hessian
        xv = np.array([1.0, 2.0], dtype=np.float32)
        x = t(xv)
        y = (x ** 3).sum()
        H = hessian(y, x)
        assert np.allclose(H.numpy(), np.diag(6 * xv), atol=1e-4)

    def test_hessian_nondiagonal(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.autograd import hessian

        rng = np.random.default_rng(1)
        xv = rng.standard_normal((4,)).astype(np.float32)
        x = t(xv)
        y = ((x ** 2).sum()) * x.sum()
        H = hessian(y, x)
        oracle = jax.hessian(
            lambda a: jnp.sum(a ** 2) * jnp.sum(a))(jnp.asarray(xv))
        assert np.allclose(H.numpy(), np.asarray(oracle), atol=1e-4)

    def test_pylayer_double_backward(self):
        from paddle_tpu.autograd import PyLayer

        class Square(PyLayer):
            @staticmethod
            def forward(ctx, x):
                ctx.save_for_backward(x)
                return x * x

            @staticmethod
            def backward(ctx, gy):
                (x,) = ctx.saved_tensor
                return 2.0 * x * gy

        x = t([3.0])
        y = Square.apply(x).sum()
        (g,) = P.grad([y], [x], create_graph=True)   # 2x = 6
        g2 = P.grad([g.sum()], [x])[0]               # 2
        assert np.allclose(g2.numpy(), [2.0])


class TestIncubateFunctionalAutograd:
    """paddle.incubate.autograd jvp/vjp/forward_grad parity vs jax
    oracles (SURVEY.md §2.2 Autograd API / Incubate)."""

    def test_jvp_matches_jax(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.incubate import autograd as iag
        x = P.to_tensor(np.asarray([[1.0, 2.0], [3.0, 4.0]], np.float32))
        v = P.to_tensor(np.full((2, 2), 0.5, np.float32))

        def f(t):
            return (t * t).sum(axis=1)

        out, tangent = iag.jvp(f, x, v)
        ref_out, ref_tan = jax.jvp(lambda a: jnp.sum(a * a, axis=1),
                                   (x._data,), (v._data,))
        np.testing.assert_allclose(out.numpy(), np.asarray(ref_out),
                                   rtol=1e-6)
        np.testing.assert_allclose(tangent.numpy(), np.asarray(ref_tan),
                                   rtol=1e-6)

    def test_vjp_matches_backward(self):
        from paddle_tpu.incubate import autograd as iag
        x = P.to_tensor(np.asarray([1.0, 2.0, 3.0], np.float32))

        def f(t):
            return (t * t * t).sum()

        out, grad = iag.vjp(f, x)
        np.testing.assert_allclose(out.numpy(), 36.0, rtol=1e-6)
        np.testing.assert_allclose(grad.numpy(), 3 * np.asarray(
            [1.0, 4.0, 9.0]), rtol=1e-6)

    def test_vjp_multi_input_with_cotangent(self):
        from paddle_tpu.incubate import autograd as iag
        a = P.to_tensor(np.asarray([1.0, 2.0], np.float32))
        b = P.to_tensor(np.asarray([3.0, 4.0], np.float32))
        v = P.to_tensor(np.asarray([1.0, -1.0], np.float32))

        def f(x, y):
            return x * y

        out, grads = iag.vjp(f, [a, b], v)
        ga, gb = grads
        np.testing.assert_allclose(ga.numpy(), [3.0, -4.0], rtol=1e-6)
        np.testing.assert_allclose(gb.numpy(), [1.0, -2.0], rtol=1e-6)

    def test_forward_grad_through_framework_ops(self):
        from paddle_tpu.incubate import autograd as iag
        x = P.to_tensor(np.asarray([[0.5, -0.5]], np.float32))
        lin = P.nn.Linear(2, 3)

        def f(t):
            return P.nn.functional.relu(lin(t)).sum()

        tangent = iag.forward_grad(f, x)
        # oracle: reverse-mode grad dotted with ones tangent
        xe = P.to_tensor(np.asarray([[0.5, -0.5]], np.float32),
                         stop_gradient=False)
        loss = P.nn.functional.relu(lin(xe)).sum()
        loss.backward()
        np.testing.assert_allclose(float(tangent.numpy()),
                                   float(xe.grad.numpy().sum()),
                                   rtol=1e-5)


class TestGradModeThreadLocal:
    """Round-11 regression: grad mode is THREAD-LOCAL. The serving tier
    runs several engine loop threads whose steps sit inside no_grad; a
    process-global flag let an unlucky cross-thread __enter__/__exit__
    interleaving restore another thread's False and disable autograd
    for the rest of the process (every later backward() raised
    "does not require grad")."""

    def test_no_grad_in_other_thread_does_not_leak(self):
        import threading

        entered = threading.Event()
        release = threading.Event()

        def holder():
            with P.no_grad():
                entered.set()
                release.wait(30)

        th = threading.Thread(target=holder, daemon=True)
        th.start()
        assert entered.wait(30)
        try:
            # another thread is INSIDE no_grad right now; this thread's
            # mode must be unaffected and backward must work
            assert P.is_grad_enabled()
            x = t([2.0, 3.0])
            (x * x).sum().backward()
            np.testing.assert_allclose(x.grad.numpy(), [4.0, 6.0],
                                       rtol=1e-6)
        finally:
            release.set()
            th.join(30)
        assert P.is_grad_enabled()

    def test_interleaved_exit_cannot_disable_process(self):
        import threading

        a_entered = threading.Event()
        b_entered = threading.Event()
        a_exited = threading.Event()

        def a():
            with P.no_grad():
                a_entered.set()
                b_entered.wait(30)
            a_exited.set()

        def b():
            a_entered.wait(30)
            with P.no_grad():   # pre-fix: saves prev=False from a
                b_entered.set()
                a_exited.wait(30)
            # pre-fix: restores False here, disabling grad globally

        ta = threading.Thread(target=a, daemon=True)
        tb = threading.Thread(target=b, daemon=True)
        ta.start()
        tb.start()
        ta.join(30)
        tb.join(30)
        assert P.is_grad_enabled()
        x = t([1.5])
        (x * x).sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), [3.0], rtol=1e-6)


# ---------------------------------------------------------------------------
# An eager training loop reaches a steady state: after the first two
# steps (step 1 still compiles what the optimizer's new state reaches)
# no call compiles anything. A function handed to apply(), or a scan
# body, that is a new object on every call would be compiled by XLA on
# every call — YOLOv3.get_loss did so six times a step, the BiGRU-CRF
# eight times.

def _yolov3_head_loop():
    """YOLOv3's own heads, get_loss, backward and Adam, eagerly, on fixed
    neck features (the whole net's first two eager steps alone are ~600
    per-op compiles; the recompile a call was in get_loss)."""
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.vision.models.yolov3 import YOLOv3, YOLOv3Config

    P.seed(0)
    m = YOLOv3(YOLOv3Config.tiny())
    m.train()
    heads = (m.head5, m.head4, m.head3)
    opt = Adam(3e-3, parameters=[p for h in heads for p in h.parameters()])
    rng = np.random.default_rng(0)
    feats = [t(rng.standard_normal((1, h.weight.shape[1], s, s)), sg=True)
             for h, s in zip(heads, (2, 4, 8))]
    gb = t([[[0.375, 0.5, 0.5, 0.5]]], sg=True)
    gl = P.to_tensor(np.array([[1]], np.int32))

    def step():
        loss = m.get_loss([h(f) for h, f in zip(heads, feats)], gb, gl)
        loss.backward()
        opt.step()
        opt.clear_grad()
    return step


def _bigru_crf_loop():
    from paddle_tpu import nn
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.text import LinearChainCrf, LinearChainCrfLoss

    P.seed(4)
    emb, gru = nn.Embedding(40, 32), nn.GRU(32, 16, direction="bidirect")
    proj, crf = nn.Linear(32, 3), LinearChainCrf(3)
    loss_fn = LinearChainCrfLoss(crf)
    opt = Adam(5e-3, parameters=[p for layer in (emb, gru, proj, crf)
                                 for p in layer.parameters()])
    rng = np.random.default_rng(0)
    lengths = P.to_tensor(np.full((16,), 12, np.int64))

    def step():
        ids = P.to_tensor(rng.integers(0, 40, (16, 12)).astype(np.int64))
        tags = P.to_tensor(rng.integers(0, 3, (16, 12)).astype(np.int64))
        loss = loss_fn(proj(gru(emb(ids))[0]), lengths, tags)
        loss.backward()
        opt.step()
        opt.clear_grad()
    return step


@pytest.mark.parametrize("make_loop", [_yolov3_head_loop, _bigru_crf_loop],
                         ids=["yolov3", "bigru_crf"])
def test_eager_loop_compiles_nothing_after_step_1(make_loop, caplog):
    import logging

    import jax

    step = make_loop()
    step()
    step()
    with jax.log_compiles(), caplog.at_level(logging.WARNING, logger="jax"):
        step()
        step()
    compiled = [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("Finished XLA compilation")]
    assert compiled == []
