"""Faults planted under the timed path: what ``tests/test_faults.py`` (at the
rehearsal size) and ``tools/readings.py`` (on the chip, at the cell's own
size) put under a built network before its first steps. They reach into the
program's internals on purpose; the benchmark's own runs never import this."""

FAULTS = ("state_unchanged", "half_batch")


def break_net(net, fault):
    if fault == "state_unchanged":
        # a step that returns its state unchanged
        net._dp_apply_updates = \
            lambda params, opt_state, grads, fused=None: (params, opt_state)
        return net
    if fault != "half_batch":
        raise ValueError(fault)
    loss = net._loss

    def half(tree):
        import jax
        return jax.tree_util.tree_map(lambda a: a[: a.shape[0] // 2], tree)

    def part_loss(params, state, x, y, *rest, **kw):
        # half of the batch left out, the mean taken over the rest
        return loss(params, state, half(x), half(y), *rest, **kw)

    net._loss = part_loss
    return net
