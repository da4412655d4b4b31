"""Test harness: run everything on a virtual 8-device CPU mesh.

Must set env before jax's backend initializes — this conftest is imported by
pytest before any test module. Multi-device sharding tests rely on the 8
virtual CPU devices (the reference has no distributed tests at all; this is
the fake-backend mechanism SURVEY.md S4 calls for). Set AF2TPU_TEST_TPU=1 to
run the suite on real accelerators instead.
"""

import os

if not os.environ.get("AF2TPU_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture
def strict_promotion():
    """Opt-in graph-hygiene fixture: every trace inside the test runs under
    strict dtype promotion, so an implicit bool/int-into-float promotion
    raises instead of silently widening — the runtime twin of the jaxpr
    auditor's AF2A105 rule (alphafold2_tpu/analysis/jaxpr_audit.py).

    List setup fixtures BEFORE this one in the test signature: fixtures
    instantiate in signature order, so earlier setup stays outside the
    strict context.
    """
    import jax

    with jax.numpy_dtype_promotion("strict"):
        yield


@pytest.fixture
def no_implicit_transfers():
    """Opt-in graph-hygiene fixture: any implicit host<->device transfer
    inside the test raises (jax.transfer_guard("disallow")). Explicit
    jax.device_put / jax.device_get remain allowed — which is the point:
    the serve/train hot paths must only ever transfer explicitly.

    Setup that builds params or PRNG keys (jax.random.key transfers its
    seed scalar) belongs in a fixture listed BEFORE this one.
    """
    import jax

    with jax.transfer_guard("disallow"):
        yield


@pytest.fixture
def kernel_path_on_the_cpu(monkeypatch):
    """The language models' splash path wherever the process is: off the TPU
    ``ops/mla.py`` ``causal_core`` builds the kernel in Pallas interpret
    mode, from 128 positions up as on the chip."""
    from alphafold2_tpu.ops import mla

    monkeypatch.setattr(mla, "causal_kernel_takes", lambda n: n >= 128)
    cached = mla._causal_kernel
    cached.cache_clear()
    yield
    cached.cache_clear()


@pytest.fixture
def named_eqns():
    """``named_eqns(primitive, fn, *args)``: the equations of that primitive
    (``"pallas_call"``: a kernel call; ``"name"``: a ``checkpoint_name``) in
    ``fn``'s jaxpr, counted by their ``name``. Every site counts, wherever
    it sits (a jitted call, a remat's recomputation, the bodies ``jax.grad``
    has made of a custom VJP); the pretty-printed jaxpr would not do, it
    prints a shared sub-jaxpr once."""
    import collections

    import jax

    from alphafold2_tpu.analysis.jaxpr_audit import iter_eqns

    def count(primitive, fn, *args):
        return collections.Counter(
            eqn.params["name"]
            for eqn in iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == primitive)

    return count


@pytest.fixture
def kept_across_remat(capsys):
    """``kept_across_remat(layer, *args)``: what
    ``jax.ad_checkpoint.print_saved_residuals`` lists for the gradient of a
    Flax ``layer``'s first output (type and shape, sorted), the layer's own
    arguments (parameters, inputs) and constants (a mask's block schedule)
    left out: what a remat'd layer keeps between forward and backward."""
    import jax

    def kept(layer, *args):
        variables = jax.eval_shape(layer.init, jax.random.key(0), *args)
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(
            lambda p, *a: layer.apply(p, *a)[0], variables, *args)
        return sorted(
            line.split()[0] for line in capsys.readouterr().out.splitlines()
            if " from the argument " not in line
            and " from a constant" not in line)

    return kept


class LockWitness:
    """Test-only instrumented-lock recorder for validating the static
    lock-order graph (alphafold2_tpu/analysis/concurrency.py) against
    runtime reality.

    ``wrap(obj, attr, label)`` replaces a ``threading`` lock attribute
    with a transparent proxy; every acquisition made while another
    wrapped lock is held on the same thread records the observed edge
    ``(held_label, acquired_label)``. Threaded slow-tier tests then
    assert every observed edge appears in the static graph — the model
    validates against reality, and a runtime acquisition the auditor
    cannot see statically fails loudly instead of silently diverging.
    """

    def __init__(self):
        import threading

        self._tls = threading.local()
        self._rec_lock = threading.Lock()
        self.edges = set()  # {(held_label, acquired_label)}

    def _held(self):
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    class _Proxy:
        def __init__(self, witness, inner, label):
            self._w = witness
            self._inner = inner
            self._label = label

        def acquire(self, *a, **k):
            got = self._inner.acquire(*a, **k)
            if got is not False:
                held = self._w._held()
                if held:
                    with self._w._rec_lock:
                        self._w.edges.add((held[-1], self._label))
                held.append(self._label)
            return got

        def release(self):
            held = self._w._held()
            if self._label in held:
                held.remove(self._label)
            return self._inner.release()

        def __enter__(self):
            self.acquire()
            return self

        def __exit__(self, *exc):
            self.release()
            return False

        def __getattr__(self, name):
            # Condition.wait/notify, Semaphore internals, etc. pass through;
            # wait() releases and re-acquires the underlying lock itself, so
            # the held stack is intentionally left alone across it
            return getattr(self._inner, name)

    def wrap(self, obj, attr: str, label: str):
        setattr(obj, attr, self._Proxy(self, getattr(obj, attr), label))
        return obj

    def wrap_class(self, cls, attr: str, label: str):
        """Monkeypatch ``cls.__init__`` so every future instance gets its
        ``attr`` lock wrapped. Returns an undo callable."""
        orig = cls.__init__

        def patched(inner_self, *a, **k):
            orig(inner_self, *a, **k)
            self.wrap(inner_self, attr, label)

        cls.__init__ = patched
        return lambda: setattr(cls, "__init__", orig)


@pytest.fixture
def lock_witness():
    """Opt-in concurrency fixture: a fresh LockWitness per test. Wrap the
    locks under test, run the threaded scenario, then assert
    ``witness.edges`` is a subpath of the static lock graph (see
    tests/test_concurrency_audit.py::test_runtime_order_matches_static)."""
    return LockWitness()
