"""Abstract (zero-device-compute) first-call semantics: compile()'s dry
run and the first train step materialise state by tracing, not executing
(the reference's buffered first call, model.py:56-91 — the eager
alternative is one device dispatch per op)."""

import numpy as np
import jax
import pytest

from singa_tpu import autograd, device, layer, model, opt
from singa_tpu.tensor import Tensor

DEV = device.create_cpu_device()


class Probe(layer.Layer):
    """Records whether its input was abstract (a tracer) when called."""

    def __init__(self, log):
        super().__init__()
        self._log = log

    def forward(self, x):
        self._log.append(isinstance(x.data, jax.core.Tracer))
        return x


def make_model(log):
    class Net(model.Model):
        def __init__(self):
            super().__init__()
            self.fc1 = layer.Linear(8)
            self.probe = Probe(log)
            self.relu = layer.ReLU()
            self.fc2 = layer.Linear(3)
            self.loss_fn = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc2(self.relu(self.probe(self.fc1(x))))

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.loss_fn(out, y)
            self.optimizer(loss)
            return out, loss
    return Net()


class TestAbstractInit:
    def test_compile_dry_run_is_abstract(self):
        log = []
        m = make_model(log)
        x = Tensor(data=np.random.randn(4, 6).astype(np.float32),
                   device=DEV, requires_grad=False)
        m.compile([x], is_train=True, use_graph=True)
        # the dry run must have traced, not executed — a silent eager
        # fallback would record False here
        assert log == [True], log
        # params exist and are concrete
        for k, v in m.get_states().items():
            assert not isinstance(v.data, jax.core.Tracer), k

    def test_first_train_step_is_abstract_then_compiled(self):
        log = []
        m = make_model(log)
        m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
        x = Tensor(data=np.random.randn(4, 6).astype(np.float32),
                   device=DEV, requires_grad=False)
        y = Tensor(data=np.eye(3)[np.random.randint(0, 3, 4)]
                   .astype(np.float32), device=DEV, requires_grad=False)
        m.compile([x], is_train=True, use_graph=True)
        log.clear()
        out, loss = m(x, y)          # first call: abstract + compiled
        assert all(log), log          # never executed eagerly
        assert np.isfinite(float(np.asarray(loss.data)))
        # optimizer aux materialised concretely by the abstract rehearsal
        aux = m.optimizer._aux
        assert aux, "momentum aux expected"
        for k, v in aux.items():
            assert not isinstance(v.data, jax.core.Tracer), k

    def test_trajectory_matches_eager_first_step(self, monkeypatch):
        def run(eager):
            if eager:
                monkeypatch.setenv("SINGA_EAGER_FIRST_STEP", "1")
            else:
                monkeypatch.delenv("SINGA_EAGER_FIRST_STEP",
                                   raising=False)
            dev = device.create_cpu_device()
            dev.SetRandSeed(3)
            m = make_model([])
            m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
            rng = np.random.RandomState(0)
            x = Tensor(data=rng.randn(4, 6).astype(np.float32),
                       device=dev, requires_grad=False)
            y = Tensor(data=np.eye(3)[rng.randint(0, 3, 4)]
                       .astype(np.float32), device=dev,
                       requires_grad=False)
            m.compile([x], is_train=True, use_graph=True)
            return [float(np.asarray(m(x, y)[1].data)) for _ in range(5)]

        np.testing.assert_allclose(run(False), run(True), rtol=1e-5)

    def test_host_side_op_falls_back_to_eager(self):
        """A train_one_batch that concretizes values cannot trace
        abstractly; the eager fallback must keep it working."""
        class Net(model.Model):
            def __init__(self):
                super().__init__()
                self.fc = layer.Linear(3)
                self.loss_fn = layer.SoftMaxCrossEntropy()

            def forward(self, x):
                return self.fc(x)

            def train_one_batch(self, x, y):
                out = self.forward(x)
                float(np.asarray(out.data)[0, 0])   # host concretization
                loss = self.loss_fn(out, y)
                self.optimizer(loss)
                return out, loss

        x = Tensor(data=np.random.randn(4, 6).astype(np.float32),
                   device=DEV, requires_grad=False)
        y = Tensor(data=np.eye(3)[np.random.randint(0, 3, 4)]
                   .astype(np.float32), device=DEV, requires_grad=False)
        # graph mode: the abstract rehearsal fails cleanly and the first
        # step falls back to eager (host-side code can never jit — with
        # graph mode such models have always needed use_graph=False)
        m = Net()
        m.set_optimizer(opt.SGD(lr=0.1))
        m.compile([x], is_train=True, use_graph=True)
        out, loss = m(x, y)
        assert np.isfinite(float(np.asarray(loss.data)))
        # eager mode trains fully
        m2 = Net()
        m2.set_optimizer(opt.SGD(lr=0.1))
        m2.compile([x], is_train=True, use_graph=False)
        losses = [float(np.asarray(m2(x, y)[1].data)) for _ in range(3)]
        assert all(np.isfinite(losses)), losses


class TestTraceOnce:
    def test_compiled_step_never_retraces(self):
        """The trace-once/replay contract (the reference scheduler's
        buffered-graph semantics, test_scheduler.cc RunGraph): after the
        first call compiles the step, later calls replay the executable
        without re-entering Python — a silent retrace-per-call would
        pay the whole trace on every step."""
        log = []
        m = make_model(log)
        m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
        rng = np.random.RandomState(0)
        x = Tensor(data=rng.randn(4, 6).astype(np.float32),
                   device=DEV, requires_grad=False)
        y = Tensor(data=np.eye(3)[rng.randint(0, 3, 4)]
                   .astype(np.float32), device=DEV, requires_grad=False)
        m.compile([x], is_train=True, use_graph=True)
        m(x, y)
        n_after_first = len(log)
        for _ in range(5):
            m(x, y)
        assert len(log) == n_after_first, \
            f"forward re-entered {len(log) - n_after_first} times"

    def test_new_signature_traces_once_more(self):
        """A different input shape compiles its own executable exactly
        once; the original signature keeps replaying its cache."""
        log = []
        m = make_model(log)
        m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
        rng = np.random.RandomState(0)

        def batch(n):
            x = Tensor(data=rng.randn(n, 6).astype(np.float32),
                       device=DEV, requires_grad=False)
            y = Tensor(data=np.eye(3)[rng.randint(0, 3, n)]
                       .astype(np.float32), device=DEV,
                       requires_grad=False)
            return x, y

        x4, y4 = batch(4)
        m.compile([x4], is_train=True, use_graph=True)
        m(x4, y4)
        base = len(log)
        x2, y2 = batch(2)
        m(x2, y2)                      # new signature: traces again
        after_new = len(log)
        assert after_new > base
        for _ in range(3):             # both signatures now cached
            m(x4, y4)
            m(x2, y2)
        assert len(log) == after_new, "a cached signature retraced"

    def test_a_key_healed_after_a_traced_draw_keeps_its_device(self):
        """A draw inside a trace (a rematerialised block in the dry run)
        leaves a dead key behind; the key healed from it lies where a
        seeded key lies, so the compiled step is handed a key of the
        placement it hands back and compiles once."""
        dev = device.create_cpu_device()
        dev.SetRandSeed(3)
        assert dev.current_key().committed
        jax.eval_shape(lambda: dev.rand_key())      # leaks a tracer
        assert isinstance(dev._get_rng_state(), jax.core.Tracer)
        dev.rand_key()
        assert not isinstance(dev._get_rng_state(), jax.core.Tracer)
        assert dev.current_key().committed
