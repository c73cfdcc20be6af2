"""Device-resident input prefetch.

The containers' streamed fit path used to hand each host batch to the jit
boundary at the moment it was needed, so the host→device copy of batch k+1
could only start after the step on batch k was dispatched — over a
fixed-bandwidth host link (PCIe) the transfer serializes with compute.
``DevicePrefetcher`` double/triple-buffers instead: it keeps up to
``depth`` batches already moved onto the device with ``jax.device_put``
ahead of consumption, so the H2D transfer of batch k+1 is in flight while
the compiled step for batch k executes (jax transfers are async:
``device_put`` dispatches and returns immediately).

This is the device-side half of the input pipeline; the host-side half —
decode/augment concurrency — is ``AsyncDataSetIterator(workers=N)``
(data/iterators.py). Composed, the three stages (parallel decode → H2D
double-buffer → compiled step) overlap fully, the tf.data recipe (Murray et
al., VLDB 2021) applied to this framework's iterator contract. Wire-dtype
note: compose with a ``device_side`` normalizer (data/normalizers.py) so
uint8 image batches cross the link raw and the f32 cast/scale runs on chip.

The prefetcher is payload-agnostic: items may be DataSets, tuples/lists of
arrays, or any nesting of them; every numpy/jax array leaf is device_put.
Per-stage costs (``fetch`` = pulling the upstream iterator, ``h2d`` =
device_put dispatch) and the bytes put on the device are recorded into an
optional ``util.timing.PipelineTimer`` so callers can report a host-stall
fraction and the bytes staged per step.
"""

from __future__ import annotations

from collections import deque

import numpy as np

from deeplearning4j_tpu.monitor.tracing import trace


def _device_put_tree(item, device=None, staged=None):
    """device_put every array leaf of a DataSet / tuple / list / dict;
    ``staged``, a list, receives each leaf's ``nbytes``."""
    import jax
    from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet

    def put(a):
        if a is None:
            return None
        out = jax.device_put(a, device)
        if staged is not None:
            staged.append(out.nbytes)
        return out

    if isinstance(item, DataSet):
        return DataSet(put(item.features), put(item.labels),
                       put(item.features_mask), put(item.labels_mask))
    if isinstance(item, MultiDataSet):
        return MultiDataSet(
            features=[put(f) for f in item.features],
            labels=[put(l) for l in item.labels],
            features_masks=None if item.features_masks is None else
            [put(m) for m in item.features_masks],
            labels_masks=None if item.labels_masks is None else
            [put(m) for m in item.labels_masks])
    if isinstance(item, tuple):
        return tuple(_device_put_tree(x, device, staged) for x in item)
    if isinstance(item, list):
        return [_device_put_tree(x, device, staged) for x in item]
    if isinstance(item, dict):
        return {k: _device_put_tree(v, device, staged)
                for k, v in item.items()}
    if isinstance(item, (np.ndarray, np.generic)) or hasattr(item, "devices"):
        return put(item)
    return item               # strings/ints/None ride through untouched


class DevicePrefetcher:
    """Iterator adapter that stages up to ``depth`` upstream items on the
    device ahead of consumption.

    ``__next__`` returns the oldest staged item and immediately tops the
    buffer back up, so by the time the caller dispatches its step the next
    batch's transfer is already in flight. ``depth=2`` double-buffers
    (enough when transfer ≤ step time); ``depth=3`` absorbs jittery
    upstream fetch. Memory cost is ``depth`` batches of device HBM.

    ``device``: where items go — a device, a sharding, or a function of
    the item returning one (the containers pass the executor's batch
    sharding, so on a multi-chip mesh a batch arrives already split over
    the chips and never whole on the first); None is the default device.

    ``transform``: optional function applied to each item AFTER the
    device_put (e.g. a jitted device-side normalizer — uint8 wire, f32
    cast/scale on chip). ``timer``: optional PipelineTimer receiving
    ``fetch``/``h2d`` stage costs.
    """

    def __init__(self, source, depth: int = 2, device=None, transform=None,
                 timer=None):
        self.source = source
        self.depth = max(1, int(depth))
        self.device = device
        self.transform = transform
        self.timer = timer
        self._it = None
        self._buf = deque()
        self._exhausted = False

    # number of batches currently staged on device (≥1 mid-stream is the
    # overlap invariant the smoke test pins)
    @property
    def buffered(self) -> int:
        return len(self._buf)

    def __iter__(self):
        if hasattr(self.source, "reset"):
            self.source.reset()
        self._it = iter(self.source)
        self._buf.clear()
        self._exhausted = False
        return self

    def _fill(self):
        import time as _time
        while len(self._buf) < self.depth and not self._exhausted:
            try:
                item = next(self._it)
            except StopIteration:
                self._exhausted = True
                break
            t1 = _time.perf_counter()
            nbytes = []
            with trace.span("h2d"):
                device = (self.device(item) if callable(self.device)
                          else self.device)
                staged = _device_put_tree(item, device, nbytes)
                if self.transform is not None:
                    staged = self.transform(staged)
            # upstream stages (fetch/decode) time themselves; only the
            # device_put dispatch is this stage's own cost
            if self.timer is not None:
                self.timer.add("h2d", _time.perf_counter() - t1)
                self.timer.bytes_staged += sum(nbytes)
            self._buf.append(staged)

    def __next__(self):
        if self._it is None:
            self.__iter__()
        if not self._buf:
            self._fill()
        if not self._buf:
            raise StopIteration
        item = self._buf.popleft()
        # top up BEFORE returning: the next batch's H2D dispatch overlaps
        # the step the caller is about to run on ``item``
        self._fill()
        return item
