"""The program's own tracing: named scopes in the INL epoch program, and
host spans in the runner and the device prefetcher (repro/tracing.py).

The scopes are checked where they are made, in the jaxpr's name stacks,
and where a trace reads them, in the compiled program's `op_name`s; the
spans in a profiler trace of two epochs of `run_scheme` on the CPU."""
import glob
import os
import re

import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_inl import PaperExperimentConfig
from repro.core import schemes
from repro.core.schemes import runner

CFG = PaperExperimentConfig(conv_channels=(4,), d_bottleneck=8,
                            dense_units=(32,), image_shape=(16, 16, 3),
                            dataset_size=128)
K, B = 2, 8
SCOPES = ("encoder", "cut", "decoder", "loss", "optimizer")
# the cut layer's kernel call runs outside every scope, which would rename
# its instruction; its jit marks it as the cut's
SCOPE_PART = re.compile(r"^(?:(?:jvp|transpose|vmap)\()*"
                        r"(%s|jit\(_cutlayer_call\))\)*$" % "|".join(SCOPES))
# equations of the scan body outside every scope: picking the round's
# views, labels and key out of the scan's stacked inputs, and splitting
# the round's key between the cut's noise and the decoder's dropout
PLUMBING = {"slice", "squeeze", "random_wrap", "random_split",
            "random_unwrap"}


def _epoch_and_args():
    scheme = schemes.get("inl")
    state = scheme.init(CFG, jax.random.PRNGKey(0))
    epoch = scheme.make_epoch(CFG)
    views = jnp.zeros((K, 1, CFG.num_clients, B) + CFG.image_shape)
    labels = jnp.zeros((K, 1, B), jnp.int32)
    rngs = jax.random.split(jax.random.PRNGKey(1), K)
    return epoch, (state, views, labels, rngs)


def _subjaxprs(eqn):
    for p in eqn.params.values():
        for x in (p if isinstance(p, (tuple, list)) else [p]):
            if isinstance(x, jex.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex.core.Jaxpr):
                yield x


def _scan_body_eqns(jaxpr, stack="", in_scan=False):
    """(primitive, name stack) of every equation without a sub-jaxpr
    inside the epoch's scan, with the stacks of the equations around it."""
    for eqn in jaxpr.eqns:
        ns = f"{stack}/{eqn.source_info.name_stack}"
        subs = list(_subjaxprs(eqn))
        inner = ns + (f"/jit({eqn.params['name']})"
                      if eqn.primitive.name == "jit" else "")
        for sub in subs:
            yield from _scan_body_eqns(sub, inner,
                                       in_scan or eqn.primitive.name == "scan")
        if not subs and in_scan:
            yield eqn.primitive.name, ns


def _scope(stack):
    for part in reversed(stack.split("/")):
        m = SCOPE_PART.match(part)
        if m:
            scope = "cut" if m.group(1).startswith("jit(") else m.group(1)
            return scope, "transpose(" in stack
    return None, False


def test_scopes_cover_the_scan_body_forward_and_backward():
    epoch, args = _epoch_and_args()
    eqns = list(_scan_body_eqns(jax.make_jaxpr(epoch)(*args).jaxpr))
    assert len(eqns) > 100
    seen = set()
    for prim, stack in eqns:
        scope, backward = _scope(stack)
        if scope is None:
            assert prim in PLUMBING, (prim, stack)
        seen.add((scope, backward))
    want = {(s, False) for s in SCOPES} | {(s, True) for s in SCOPES
                                          if s != "optimizer"}
    assert want <= seen


def test_compiled_epoch_program_carries_the_scopes_in_op_name():
    epoch, args = _epoch_and_args()
    text = epoch.lower(*args).compile().as_text()
    parts = {p for name in re.findall(r'op_name="([^"]*)"', text)
             for p in name.split("/")}
    for s in ("encoder", "decoder", "loss", "jit(_cutlayer_call)"):
        assert f"jvp({s})" in parts and f"transpose(jvp({s}))" in parts, s
    assert "jvp(cut)" in parts and "optimizer" in parts


def _trace_events(trace_dir):
    """(line index, name, stats) of the host's `repro.` and `test.` spans
    in a CPU trace."""
    from jax.profiler import ProfileData
    out = []
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(("repro.", "test.")):
                        out.append(((plane.name, i), e.name,
                                    {k: v for k, v in e.stats}))
    return out


def test_runner_and_prefetcher_spans_in_a_trace(tmp_path):
    n, b = 64, 16
    rng = np.random.default_rng(0)
    views = rng.standard_normal((CFG.num_clients, n) + CFG.image_shape,
                                dtype=np.float32)
    labels = rng.integers(0, CFG.num_classes, n).astype(np.int32)
    kw = dict(epochs=2, batch_size=b, eval_n=16, dispatch="scan")
    runner.run_scheme("inl", views, labels, CFG, **kw)   # compile first
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.main"):
            runner.run_scheme("inl", views, labels, CFG, **kw)
    events = _trace_events(str(tmp_path))
    by_name = {}
    for line, name, stats in events:
        by_name.setdefault(name, []).append((line, stats))
    rounds = n // b
    # the item: the (K, 1, b) int32 index matrix, the labels it picks and
    # the round keys; the superbatch is gathered from the resident views
    item_bytes = (4 * rounds * b + 4 * rounds * b
                  + rounds * jax.random.PRNGKey(0).nbytes)
    superbatch_bytes = views[:, :rounds * b].nbytes
    assemble = by_name["repro.runner.assemble"]
    put = by_name["repro.prefetch.put"]
    assert len(assemble) == len(put) == 2
    for _, stats in assemble:
        assert stats["rounds"] == rounds and stats["bytes"] == item_bytes
    assert all(stats["bytes"] == item_bytes for _, stats in put)
    # the producer thread assembles and puts; the consumer, the runner's
    # own thread, waits
    producer = {line for line, _ in assemble + put}
    main = {line for line, _ in by_name["test.main"]}
    waits = {line for line, _ in by_name["repro.prefetch.wait"]}
    assert len(producer) == 1 and producer != main and waits == main
    assert len(by_name["repro.prefetch.wait"]) == 2   # one pull an epoch
    # the runner's thread gathers each epoch's superbatch on the device
    # from the view set it made resident once
    gather = by_name["repro.runner.gather"]
    assert len(gather) == 2 and {line for line, _ in gather} == main
    assert all(stats["rounds"] == rounds
               and stats["bytes"] == superbatch_bytes for _, stats in gather)
    resident = by_name["repro.runner.resident"]
    assert len(resident) == 1 and resident[0][1]["bytes"] == views.nbytes
