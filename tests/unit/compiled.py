"""What the files of ``tests/unit`` compile ONCE a process and share.

An adapter's primitive called eagerly dispatches some hundred small
operations a call for a two-layer block, and compiles each of them anew a
shape: a case whose point is what the primitive COMPUTES calls it through
``compiled``. A case whose point is the eager path itself keeps its own call
and says so.
"""

import jax

_COMPILED = {}


def compiled(adapter, name):
    """``adapter.<name>`` under ONE ``jax.jit`` an adapter (adapters are
    hashable static configuration), shared by every case that calls it."""
    if (adapter, name) not in _COMPILED:
        _COMPILED[adapter, name] = jax.jit(getattr(adapter, name))
    return _COMPILED[adapter, name]


_ALONE = {}


def served_alone(engine, model, prompt, n, fresh=False, **kw):
    """The ``n`` greedy tokens ``prompt`` gets from ``engine(model, **kw)``
    with nothing beside it. ONE engine a (model, configuration) serves every
    such request of the process, one after another, each into slots the last
    one freed: a case that wants a neighbour's stream compared with the
    stream alone pays a request, not an engine's compile. That a freed slot
    serves as a new one does is a property with cases of its own
    (``test_a_reused_slot_gives_the_stream_it_gives_alone`` and its like),
    and THEY ask for ``fresh`` engines."""
    if fresh:
        eng = engine(model, **kw)
    else:
        key = (id(model[1]), tuple(sorted(kw.items())))
        if key not in _ALONE:
            # the model kept beside it: its id stays its own
            _ALONE[key] = (engine(model, **kw), model)
        eng = _ALONE[key][0]
    req = eng.submit(prompt, max_new_tokens=n)
    eng.run()
    assert eng.compile_count == 1
    return req.tokens
