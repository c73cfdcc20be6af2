"""How many host batches one ``fit_scan`` call takes on the streamed fit
path of both containers.

Stacking batches into one device-resident call pays where a step is so
light that the host's work per call shows: on a v5e a ``train_step`` call
through ``fit`` costs the host about 2 ms and a ``fit_scan`` call about
6 ms, whatever it holds (PERF.md, PR 30), so a step shorter than that is
worth stacking and a longer one is not, and a chunk of two or three never
is. Two things decide: the bytes staged on the host for a chunk, and
whether the step is heavy. A batch of token ids is a few kilobytes and may
still be a step of tens of TFLOP, so bytes alone do not say that a step is
light: its work is estimated from what the program can see, ``6 x
parameters x rows`` (forward and backward of every weight once per row),
and a step estimated above ``STEP_MAX_FLOPS`` goes singly, as
``train_step``. A row is what the loss is taken over or a time step that is
fed in, read from the batch's structure and never from a feature's dtype:
the image iterators ship raw uint8 pixels, which are not tokens. Integer
labels are class ids (``nn.losses.is_class_ids``), one row each; floating
labels have one row per leading index before the class axis; a 3-D
floating feature is (batch, time, width) and has a row per time step; any
other feature a row per example. Token ids whose label is one per sequence
read as one row a sequence, lower than they are, and stay with the byte
bound as before. Convolutions reuse their weights over positions and read
lower than they are too; their batches are large and the byte bound
already holds them.
"""

from __future__ import annotations

import numpy as np

# a step estimated above this goes singly. Measured on a v5e with a dense
# net of 13 M parameters (PERF.md, PR 30, calls F and G): at 1.0e10 a step
# chunks of 64 read 3 % over single steps, at 2.0e10 single steps 6 % over
# them, at 4e10 and 3e11 single steps over every chunk length tried
STEP_MAX_FLOPS = 1.5e10


def n_parameters(params) -> int:
    import jax
    return sum(int(l.size) for l in jax.tree_util.tree_leaves(params))


def batch_rows(features, labels) -> int:
    rows = 0
    for l in labels:
        l = np.asarray(l)
        lead = l.shape if np.issubdtype(l.dtype, np.integer) else l.shape[:-1]
        rows = max(rows, int(np.prod(lead, dtype=np.int64)))
    for f in features:
        f = np.asarray(f)
        if f.ndim == 3 and np.issubdtype(f.dtype, np.floating):
            rows = max(rows, int(f.shape[0] * f.shape[1]))
        elif f.ndim:
            rows = max(rows, int(f.shape[0]))
    return rows


def steps_per_chunk(features, labels, n_params, max_steps, max_bytes) -> int:
    """Steps of one chunk for batches like this one: one where the step's
    estimated work is over ``STEP_MAX_FLOPS``; else at most ``max_steps``
    and at most ``max_bytes`` of host batches, at least one."""
    if 6.0 * n_params * batch_rows(features, labels) > STEP_MAX_FLOPS:
        return 1
    per = sum(np.asarray(a).nbytes for a in list(features) + list(labels))
    return int(max(1, min(max_steps, max_bytes // max(1, per))))
