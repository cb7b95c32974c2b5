"""Reduce a gradient where the backward pass completes it.

The reference overlaps the gradient reduction with backprop by hooking
every tensor: the hook enqueues the allreduce the moment that tensor's
gradient is ready.  The compiled train step (``ops/compiled.py``) takes
``jax.value_and_grad`` of the whole loss and reduces the gradient tree
afterwards, which is as good wherever each leaf's gradient is a value
of its own (a model whose layers are a Python loop: the reduction of a
leaf depends on nothing later, and the compiler may start it
mid-backward).  It is not for layers stacked under a scan: the gradient
of a stacked leaf is one buffer that the backward loop fills slice by
slice and that is complete only when the loop ends.

``reduce_in_backward`` is the hook for that case: identity in the
forward pass; in the backward pass the step's own reduction (the same
``lax.pmean`` / ``lax.psum`` over the same axis) applied to the
cotangent, under the step's ``hvd_step/grad_reduce`` scope.  Applied to
the per-layer parameter slice INSIDE the scan body, it puts each
layer's all-reduce into the backward loop's body, beside the previous
layer's backward.

The hook engages only while a data-parallel compiled step across more
than one device is tracing its loss for ``op`` Average or Sum: the
step opens ``reducing_in_backward`` around that trace, on the tracing
thread (rank threads share the process, so the context is
thread-local).  Outside such a context the hook returns its argument
itself: no ``custom_vjp`` in the jaxpr, the traced program unchanged.
The hook records the parameter subtree it covers; the step skips those
leaves in its reduction after the backward, so no leaf is reduced
twice (for ``op=Sum`` that would be a wrong answer, not only a wasted
collective).
"""

import contextlib
import functools
import threading

import jax

_TRACING = threading.local()


class _StepReduction:
    """What a step that is tracing its loss tells the hooks inside it:
    the reduction of one leaf, the scope to name it under, and (filled
    by the hooks) the parameter subtrees reduced in the backward."""

    __slots__ = ("reduce_leaf", "scope", "covered")

    def __init__(self, reduce_leaf, scope):
        self.reduce_leaf = reduce_leaf
        self.scope = scope
        self.covered = []


@contextlib.contextmanager
def reducing_in_backward(reduce_leaf, scope):
    """Opened by a data-parallel step around the trace of its loss and
    gradient; yields the record of what the hooks covered."""
    outer = getattr(_TRACING, "step", None)
    _TRACING.step = found = _StepReduction(reduce_leaf, scope)
    try:
        yield found
    finally:
        _TRACING.step = outer


def reduces_in_backward():
    """Whether a step that reduces gradients in the backward pass is
    tracing on this thread: what a model asks before it builds the
    module tree that carries the hook."""
    return getattr(_TRACING, "step", None) is not None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _reduce_cotangent(reduce_leaf, scope, tree):
    return tree


def _forward(reduce_leaf, scope, tree):
    return tree, None


def _backward(reduce_leaf, scope, _, cotangent):
    with jax.named_scope(scope):
        return (jax.tree.map(reduce_leaf, cotangent),)


_reduce_cotangent.defvjp(_forward, _backward)


def reduce_in_backward(tree, covers):
    """``tree`` itself; under a compiled data-parallel step, ``tree``
    with the step's gradient reduction applied to its cotangent in the
    backward pass.

    ``tree`` is the parameters of one iteration of a scan over stacked
    layers (any pytree), taken inside the scan body (and inside the
    ``jax.checkpoint`` / ``nn.remat`` wrapper, if there is one).
    ``covers`` is the path, as a tuple of keys from the root of the
    step's parameter tree, of the stacked subtree those slices come
    from (``("layers",)`` for ``TransformerLM``): every gradient leaf
    under it must flow through this hook and nowhere else, because the
    step leaves those leaves out of its own reduction."""
    step = getattr(_TRACING, "step", None)
    if step is None:
        return tree
    covers = tuple(covers)
    if covers not in step.covered:
        step.covered.append(covers)
    return _reduce_cotangent(step.reduce_leaf, step.scope, tree)
