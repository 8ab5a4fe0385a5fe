"""Domino overlap evidence at the HLO level (reference:
``deepspeed/runtime/domino/transformer.py:605`` — hand-scheduled async
TP allreduces overlapping the other half-batch's compute).

The TPU design argument is "present two independent compute→allreduce
chains; XLA's latency-hiding scheduler overlaps them". These tests stop
it being an assertion:

* CPU (always runs, subprocess): compile a TP block with
  ``domino_split`` with XLA's all-reduce combiner disabled and verify
  the *dependence structure* the scheduler needs — two distinct
  all-reduces, neither reachable from the other, and dot ops from the
  other half that are neither ancestors nor descendants of a given
  all-reduce (i.e. legally schedulable during it). Also numeric parity
  split vs unsplit.
* CPU combiner fact (always runs): at default flags the CPU backend
  COMBINES the two half all-reduces into one — recorded as a test so
  the limitation is pinned, not hidden: combining degenerates Domino to
  the unsplit schedule (same wire, no overlap, no regression either).
* TPU (runs in chip sessions): the compiled, scheduled module must show
  async ``all-reduce-start``/``all-reduce-done`` pairs with the other
  half's dots scheduled between them — the reference's overlap, done by
  the XLA scheduler instead of NoOper/HANDLE_DIC event machinery.
"""

import json
import os
import re
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Child emits one JSON line with the structural facts; it runs in a
# subprocess because XLA_FLAGS is parsed once per process.
_CHILD = r"""
import json, re
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

mesh = Mesh(np.array(jax.devices()).reshape(8), ("tensor",))

def tp_mlp(x, w1, w2):
    h = jax.nn.gelu(x @ w1)
    return jax.lax.psum(h @ w2, "tensor")

def plain(x, w1, w2):
    return tp_mlp(x, w1, w2)

def domino(x, w1, w2):
    from hcache_deepspeed_tpu.runtime.domino import domino_split
    return domino_split(lambda h: tp_mlp(h, w1, w2), x)

x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16, 64)),
                jnp.float32)
w1 = jnp.asarray(np.random.default_rng(1).normal(size=(64, 32)),
                 jnp.float32)
w2 = jnp.asarray(np.random.default_rng(2).normal(size=(32, 64)),
                 jnp.float32)

def compiled(fn):
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(), P(None, "tensor"), P("tensor",)),
        out_specs=P(), check_vma=False)).lower(x, w1, w2).compile()

def entry_graph(txt):
    # {op_name: (opcode, [operand names])} for the ENTRY computation
    lines = txt.splitlines()
    start = next(i for i, l in enumerate(lines)
                 if l.lstrip().startswith("ENTRY"))
    graph = {}
    for line in lines[start + 1:]:
        s = line.strip()
        if s == "}":
            break
        m = re.match(r"(%?[\w.\-]+) = .*?([a-z][a-z0-9\-]*)\((.*)$", s)
        if not m:
            continue
        name, opcode, rest = m.groups()
        operands = re.findall(r"%[\w.\-]+", rest.split(")")[0])
        graph[name.lstrip("%")] = (
            opcode, [o.lstrip("%") for o in operands])
    return graph

def ancestors(graph, name):
    seen, stack = set(), [name]
    while stack:
        for op in graph.get(stack.pop(), (None, []))[1]:
            if op not in seen:
                seen.add(op)
                stack.append(op)
    return seen

c_domino = compiled(domino)
g = entry_graph(c_domino.as_text())
ars = [n for n, (op, _) in g.items() if op == "all-reduce"]
dots = [n for n, (op, _) in g.items() if op == "dot"]
anc = {n: ancestors(g, n) for n in ars}
independent = (len(ars) == 2
               and ars[0] not in anc[ars[1]]
               and ars[1] not in anc[ars[0]])
overlappable = 0
if len(ars) == 2:
    for ar in ars:
        ar_anc = anc[ar]
        free = [d for d in dots
                if d not in ar_anc and ar not in ancestors(g, d)]
        overlappable += bool(free)

y_plain = compiled(plain)(x, w1, w2)
y_domino = c_domino(x, w1, w2)
parity = bool(jnp.allclose(
    jax.tree.leaves(y_plain)[0], jax.tree.leaves(y_domino)[0],
    rtol=1e-5, atol=1e-5))

print(json.dumps({"n_ar": len(ars), "n_dots": len(dots),
                  "independent": independent,
                  "overlappable_ars": overlappable,
                  "parity": parity}))
"""


def _run_child(extra_xla_flags):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + extra_xla_flags)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestDominoHLOStructure:

    def test_split_chains_are_schedulably_independent(self):
        """With the combiner out of the way, the compiled module must
        contain two all-reduces with no dependence path between them,
        and each must have other-half dots it could overlap with."""
        facts = _run_child(
            "--xla_disable_hlo_passes=cpu-all-reduce-combiner")
        assert facts["n_ar"] == 2, facts
        assert facts["independent"], facts
        # each all-reduce has at least one dot free to run during it
        assert facts["overlappable_ars"] == 2, facts
        assert facts["n_dots"] >= 4, facts
        assert facts["parity"], facts

    def test_cpu_default_combiner_fact(self):
        """Pin the backend's combiner behavior at default flags. Older
        CPU backends merged the two half all-reduces into one (Domino
        degenerated to the unsplit schedule — same math, same wire, no
        overlap); jax 0.4.37's no longer does. Either way the facts
        must stay coherent: one combined collective, OR two with the
        independence the structural test above guarantees — and parity
        always."""
        facts = _run_child("")
        assert facts["n_ar"] in (1, 2), facts
        if facts["n_ar"] == 2:
            assert facts["independent"], facts
        assert facts["parity"], facts


@pytest.mark.tpu
@pytest.mark.skipif(
    os.environ.get("HDS_TPU_TESTS") != "1",
    reason="chip-session only (set HDS_TPU_TESTS=1 with a live TPU)")
class TestDominoTPUSchedule:

    def test_async_allreduce_overlaps_other_half_dots(self):
        """On TPU the compiled module is scheduled: assert async
        all-reduce-start/done pairs exist and at least one dot sits
        between a start and its done in schedule order — the exact
        overlap the reference hand-builds."""
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)  # real backend
        # conftest's --xla_force_host_platform_device_count=8 must not
        # leak: with it, a CPU fallback presents 8 devices and compiles
        # a sync CPU all-reduce — reported as FAIL instead of the
        # honest "needs >=2 live TPU chips" skip (seen 2026-08-01).
        # Strip only that token; other operator XLA flags must reach
        # the child unchanged.
        if "XLA_FLAGS" in env:
            kept = [t for t in env["XLA_FLAGS"].split()
                    if "xla_force_host_platform_device_count" not in t]
            if kept:
                env["XLA_FLAGS"] = " ".join(kept)
            else:
                del env["XLA_FLAGS"]
        env["PYTHONPATH"] = _REPO
        out = subprocess.run(
            [sys.executable, "-c", _SCHED_CHILD], env=env,
            capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        facts = json.loads(out.stdout.strip().splitlines()[-1])
        if "skip" in facts:
            pytest.skip(f"needs >=2 live devices: {facts['skip']}")
        assert facts["async_pairs"] >= 1, facts
        assert facts["dots_inside_async_window"] >= 1, facts


# TPU child: dump the scheduled module text and measure, for each
# all-reduce-start..done window, how many dot ops are scheduled inside.
_SCHED_CHILD = r"""
import json, re
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

n = len(jax.devices())
if jax.default_backend() != "tpu":
    # a CPU run must not masquerade as a chip measurement: its
    # all-reduce is synchronous by construction
    print(json.dumps({"skip": f"backend is {jax.default_backend()!r}, "
                              "not tpu"}))
    raise SystemExit(0)
if n < 2:
    # one chip has no tensor axis to reduce over — the psum is
    # compiled away and there is nothing to schedule asynchronously
    print(json.dumps({"skip": f"single-device backend (n={n})"}))
    raise SystemExit(0)
mesh = Mesh(np.array(jax.devices()), ("tensor",))

def tp_mlp(x, w1, w2):
    h = jax.nn.gelu(x @ w1)
    return jax.lax.psum(h @ w2, "tensor")

def domino(x, w1, w2):
    from hcache_deepspeed_tpu.runtime.domino import domino_split
    return domino_split(lambda h: tp_mlp(h, w1, w2), x)

x = jnp.ones((8, 512, 1024), jnp.bfloat16)
w1 = jnp.ones((1024, 4096 // n), jnp.bfloat16)
w2 = jnp.ones((4096 // n, 1024), jnp.bfloat16)
c = jax.jit(jax.shard_map(
    domino, mesh=mesh, in_specs=(P(), P(None, "tensor"), P("tensor",)),
    out_specs=P(), check_vma=False)).lower(x, w1, w2).compile()
txt = c.as_text()
lines = [l.strip() for l in txt.splitlines()]
async_pairs = 0
dots_inside = 0
open_windows = 0
for l in lines:
    if re.search(r"= .*all-reduce-start\(", l):
        open_windows += 1
        async_pairs += 1
    elif re.search(r"= .*all-reduce-done\(", l):
        open_windows = max(0, open_windows - 1)
    elif open_windows and re.search(r"= .*\bdot\(|fusion\(", l):
        dots_inside += 1
print(json.dumps({"async_pairs": async_pairs,
                  "dots_inside_async_window": dots_inside}))
"""
