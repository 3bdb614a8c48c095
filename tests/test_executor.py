"""Local execution substrate: LocalRef, num_returns, actor serialism."""

import time

import pytest

from rayfed_tpu.executor import ActorInstance, LocalRef, TaskExecutor, is_local_refs


@pytest.fixture()
def executor():
    ex = TaskExecutor(max_workers=4)
    yield ex
    ex.shutdown()


def test_submit_and_resolve(executor):
    ref = executor.submit(lambda a, b: a + b, (1, 2), {})
    assert ref.resolve() == 3


def test_top_level_ref_resolution(executor):
    dep = executor.submit(lambda: 40, (), {})
    ref = executor.submit(lambda x: x + 2, (dep,), {})
    assert ref.resolve() == 42


def test_nested_refs_not_resolved(executor):
    dep = executor.submit(lambda: 1, (), {})

    def consumer(container):
        assert isinstance(container[0], LocalRef)
        return container[0].resolve() + 1

    ref = executor.submit(consumer, ([dep],), {})
    assert ref.resolve() == 2


def test_num_returns(executor):
    refs = executor.submit(lambda: (1, 2, 3), (), {}, num_returns=3)
    assert [r.resolve() for r in refs] == [1, 2, 3]


def test_num_returns_mismatch(executor):
    refs = executor.submit(lambda: (1, 2), (), {}, num_returns=3)
    with pytest.raises(ValueError):
        refs[0].resolve()


def test_exception_propagates(executor):
    def boom():
        raise RuntimeError("boom")

    ref = executor.submit(boom, (), {})
    with pytest.raises(RuntimeError, match="boom"):
        ref.resolve()


def test_is_local_refs():
    assert is_local_refs(LocalRef.from_value(1))
    assert is_local_refs([LocalRef.from_value(1), LocalRef.from_value(2)])
    assert not is_local_refs([LocalRef.from_value(1), 2])
    assert not is_local_refs(3)
    assert not is_local_refs([])


@pytest.mark.parametrize("chained", [False, True])
def test_done_callback_goes_with_the_last_ref_not_the_collector(chained):
    """A callback registered BEFORE its ref resolves stays on the future;
    it must not close a ref -> future -> callback -> ref cycle, or what
    it holds (a round's aggregator and codec: device buffers) waits for
    the cycle collector."""
    import gc
    import weakref

    class Held:
        pass

    held = Held()
    alive = weakref.ref(held)
    seen = []
    gc.disable()
    try:
        ref = LocalRef()
        if chained:
            out = ref.then(lambda v, held=held: seen.append(v))
        else:
            ref.add_done_callback(
                lambda r, held=held: seen.append(r.resolve())
            )
        ref.set_result(7)
        assert seen == [7]
        del held, ref
        if chained:
            del out
        assert alive() is None
    finally:
        gc.enable()


class Counter:
    def __init__(self, start):
        self.value = start

    def add(self, n):
        # Non-atomic on purpose: serial actor execution must keep it correct.
        v = self.value
        time.sleep(0.001)
        self.value = v + n
        return self.value

    def get(self):
        return self.value


def test_actor_serial_execution():
    actor = ActorInstance(Counter, (0,), {})
    refs = [actor.call_method("add", (1,), {}) for _ in range(20)]
    assert refs[-1].resolve() == 20
    assert actor.call_method("get", (), {}).resolve() == 20
    actor.kill()
    with pytest.raises(RuntimeError):
        actor.call_method("get", (), {})


def test_actor_constructor_failure_surfaces():
    class Bad:
        def __init__(self):
            raise ValueError("ctor failed")

        def m(self):
            return 1

    actor = ActorInstance(Bad, (), {})
    with pytest.raises(ValueError, match="ctor failed"):
        actor.call_method("m", (), {}).resolve()
