"""Gates on a ``DeviceBlockLoader``'s producer, so that what its
consumer finds in the queue is decided by the test and not by the
scheduler: each wraps what the producer calls once an item, one of
the loader's host reads (an instance or a class attribute) or its
``_put``."""

import threading
import time


def held_after_first(obj, name: str, timeout: float = 5.0):
    """The producer's first call of ``obj.name`` runs; every later one
    waits for the returned event. Returns ``(gate, timed_out)``: set
    ``gate`` to let the producer go on; ``timed_out`` lists the calls
    that gave up waiting (a consumer that needed a second item before
    it handed out the first makes one)."""
    real = getattr(obj, name)
    gate, calls, timed_out = threading.Event(), [], []

    def held(*a, **k):
        calls.append(1)
        if len(calls) > 1 and not gate.wait(timeout):
            timed_out.append(len(calls))
        return real(*a, **k)

    setattr(obj, name, held)
    return gate, timed_out


class Lockstep:
    """Wraps ``loader._put``: the producer puts item k (and the end of
    the pass) only once the consumer has been handed item k - 1, so
    every take of the consumer is a blocking get with nothing in
    flight. Iterate the pass through :meth:`iterate`; ``timed_out``
    counts the puts that gave up waiting and went ahead (a consumer
    that needed a second item before it handed out the first)."""

    def __init__(self, loader, timeout: float = 5.0):
        real = loader._put
        self._turns = threading.Semaphore(1)
        self.timed_out = 0

        def put(q, stop, item):
            if not self._turns.acquire(timeout=timeout):
                self.timed_out += 1
            return real(q, stop, item)

        loader._put = put

    def iterate(self, items):
        for item in items:
            self._turns.release()
            yield item


class FullQueue:
    """Wraps ``loader._put`` to see the queue, and ``loader._jax`` so
    that the consumer's FIRST ``device_put`` waits until the queue
    holds ``depth`` items: the consumer then finds a full queue before
    its first take. ``puts`` counts the consumer's ``device_put``s."""

    def __init__(self, loader, depth: int, timeout: float = 10.0):
        self.puts = 0
        self.queue = None
        real_put, jax = loader._put, loader._jax
        gate = self

        def put(q, stop, item):
            gate.queue = q
            return real_put(q, stop, item)

        class Jax:
            def __getattr__(self, attr):
                return getattr(jax, attr)

            @staticmethod
            def device_put(data, device):
                gate.puts += 1
                if gate.puts == 1:
                    q = gate.queue
                    end = time.monotonic() + timeout
                    while q.qsize() < depth and time.monotonic() < end:
                        time.sleep(0.005)
                    assert q.qsize() >= depth, "the queue never filled"
                return jax.device_put(data, device)

        loader._put = put
        loader._jax = Jax()
