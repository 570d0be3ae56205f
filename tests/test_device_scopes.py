"""The device half of ``core/trace.py``: the dense steps' named scopes,
read back from the compiled program (``program_scopes``) and laid on a
device trace's per-instruction times (``device_scope_table``), and the
benchmark's reader of it (``benchmarks/readers/trace_scope_ms_per_step``)."""

import collections
import json
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax import lax

from benchmarks.readers import trace_scope_ms_per_step as reader
from benchmarks.run import load_json, overlay
from paddlebox_tpu.core import trace
from paddlebox_tpu.models.train_step import make_train_step
from paddlebox_tpu.parallel import HybridTopology, build_mesh

PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


@pytest.fixture
def recorded(monkeypatch):
    """A program record of the test's own: nothing another test compiled."""
    programs = collections.deque(maxlen=8)
    monkeypatch.setattr(trace, "_PROGRAMS", programs)
    monkeypatch.setattr(trace, "_PARSED", {})
    return programs


class _Text:
    """What ``record_program`` needs of a compiled program."""

    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def _op_names(text):
    """instruction -> its own ``op_name`` ("" where it has none)."""
    out = {}
    for line in text.splitlines():
        m = trace._INSTRUCTION.match(line)
        if m:
            meta = trace._OP_NAME.search(m.group(2))
            out[m.group(1)] = meta.group(1) if meta else ""
    return out


def _executed(text):
    """The instructions a device trace can show: those of the entry
    computation and of the loops, branches and calls it runs (not what a
    fusion or a reduction holds)."""
    body, entry, computation = {}, None, None
    for line in text.splitlines():
        head = trace._COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            body[computation] = []
            entry = computation if line.startswith("ENTRY") else entry
        elif computation and trace._INSTRUCTION.match(line):
            body[computation].append(line)
    names, todo, seen = set(), [entry], set()
    while todo:
        computation = todo.pop()
        if computation in seen or computation not in body:
            continue
        seen.add(computation)
        for line in body[computation]:
            name, rest = trace._INSTRUCTION.match(line).groups()
            names.add(name)
            if trace._opcode(rest)[0] in trace.CONTAINERS:
                todo += trace._CALLED.findall(rest)
                for group in trace._CALLED_SET.findall(rest):
                    todo += [c.strip().lstrip("%") for c in group.split(",")]
    return names


# -- the phase and scope of one op_name --------------------------------------

@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp()/stack/while/body/closed_call/attention/dot_general",
     ("stack", "attention", "forward")),
    ("jit(step)/transpose(jvp(stack))/while/body/closed_call/checkpoint/"
     "mlp/mul", ("stack", "mlp", "backward")),
    ("jit(step)/transpose(jvp(stack))/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/tanh", ("stack", "attention",
                                             "recompute")),
    ("jit(step)/jvp()/stack/moe/jit(argsort)/attention/iota",
     ("stack", "moe", "forward")),
    ("jit(step)/jvp(head)/jit(take_along_axis)/gather",
     ("head", None, "forward")),
    ("jit(step)/transpose(jvp(embed))/transpose", ("embed", None,
                                                   "backward")),
    ("jit(step)/optimizer/add", ("optimizer", None, "forward")),
    ("jit(step)/jvp()/while/body/dynamic_slice", (None, None, "forward")),
    ("params['layers']['wqkv']", (None, None, "forward")),
    ("", (None, None, "forward")),
])
def test_scope_of_reads_the_name_stack(op_name, want):
    assert trace.scope_of(op_name) == want


# -- a scoped toy step compiled on the CPU -----------------------------------

@jax.custom_vjp
def _sin(x):
    return jnp.sin(x)


def _sin_fwd(x):
    return jnp.sin(x), x


def _sin_bwd(x, g):             # written out: the only cosine in the step
    return (g * jnp.cos(x) * 3.0,)


_sin.defvjp(_sin_fwd, _sin_bwd)


def _toy_step():
    def layer(w, h):
        with jax.named_scope("attention"):
            a = h + jnp.tanh(h @ w["a"])
        with jax.named_scope("mlp"):
            return a + _sin(a @ w["m"])

    def loss(p, x):
        with jax.named_scope("embed"):
            h = x @ p["e"]
        with jax.named_scope("stack"):
            h, _ = lax.scan(lambda h, w: (jax.checkpoint(layer)(w, h), None),
                            h, p["layers"])
        with jax.named_scope("head"):
            value = jnp.sum(h ** 2)
        return value, jnp.sort(x[0])            # the sort: in no scope

    opt = optax.sgd(0.1)
    params = {"e": jnp.ones((8, 16)),
              "layers": {"a": jnp.ones((3, 16, 16)) * 0.1,
                         "m": jnp.ones((3, 16, 16)) * 0.1}}
    x = jnp.arange(32.0).reshape(4, 8)[::-1]
    step = make_train_step(jax.value_and_grad(loss, has_aux=True), opt,
                           has_aux=True)
    return step, (params, opt.init(params), x)


@pytest.fixture
def toy(recorded):
    step, args = _toy_step()
    compiled = step.lower(*args).compile()
    return compiled, trace.program_scopes(compiled.as_text())


def test_compiling_the_step_records_it_and_calling_it_does_not(recorded):
    step, args = _toy_step()
    compiled = step.lower(*args).compile()
    assert list(recorded) == [compiled]
    got = step(*args)               # the jitted step, called as it always was
    assert list(recorded) == [compiled]
    want = compiled(*_toy_step()[1])
    assert float(got[2]) == pytest.approx(float(want[2]))


def test_the_toy_steps_phases_and_parts(toy):
    compiled, scopes = toy
    text = compiled.as_text()
    named = _op_names(text)
    found = {s for _, s in scopes.values()}
    for part in ("attention", "mlp"):
        for phase in ("forward", "backward", "recompute"):
            assert ("stack", part, phase) in found
    for top in ("embed", "head", "optimizer"):
        assert any(s[0] == top for s in found)
    # the custom_vjp's written-out backward reads as backward
    cosines = [n for n, op in named.items() if "/mlp/cos" in op]
    assert cosines and {scopes[n][1] for n in cosines} == {
        ("stack", "mlp", "backward")}
    # the scan's bookkeeping: under stack, in no part
    booked = [n for n, op in named.items()
              if re.search(r"stack.*/while/body/dynamic_(update_)?slice$", op)]
    assert booked and all(scopes[n][1][:2] == ("stack", None)
                          for n in booked)
    # the op outside every scope
    sorts = [n for n, (opcode, _) in scopes.items() if opcode == "sort"]
    assert sorts and all(scopes[n][1][0] is None for n in sorts)


def test_the_table_leaves_containers_out_and_counts_the_rest(toy):
    compiled, scopes = toy
    ops = {f"{n} ({opcode})": (1.0, 1) for n, (opcode, _) in scopes.items()}
    ops["not_in_the_step.7 (fusion)"] = (5.0, 2)
    table = trace.device_scope_table(ops)
    leaves = sum(opcode not in trace.CONTAINERS
                 for opcode, _ in scopes.values())
    assert any(op in trace.CONTAINERS for op, _ in scopes.values())
    assert table.seconds() == leaves
    assert table.elsewhere == (5.0, 2.0)
    assert table.seconds(top=None) >= 1.0               # the sort
    assert table.seconds(top="stack", part="mlp", phase="backward") > 0
    assert table.parse_s >= 0.0


def test_the_table_reads_the_program_that_covers_the_trace(toy, recorded):
    compiled, scopes = toy
    other = jax.jit(lambda x: jnp.cos(x) * 2.0).lower(
        jnp.ones((4,))).compile()
    trace.record_program(other)             # newer, and not what ran
    ops = {f"{n} ({opcode})": (1.0, 1) for n, (opcode, _) in scopes.items()
           if opcode not in trace.CONTAINERS}
    table = trace.device_scope_table(ops)
    assert table.seconds() == len(ops) and table.elsewhere == (0.0, 0.0)
    assert table.seconds(top="stack") > 0


def test_nothing_recorded_reads_nothing(recorded):
    assert trace.device_scope_table({"fusion.1 (fusion)": (1.0, 1)}) is None


# -- a hand-written module: what XLA makes with no op_name of its own -------

MODULE = """HloModule jit_step, is_scheduled=true

%fused_convert (param_0: f32[4,8]) -> bf16[4,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  ROOT %convert.1 = bf16[4,8]{1,0} convert(%param_0)
}

%body (arg: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %arg = (s32[], bf16[4,8]{1,0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  %gte.1 = bf16[4,8]{1,0} get-tuple-element(%arg), index=1
  %copy.9 = bf16[4,8]{1,0} copy(%gte.1)
  %dot.3 = bf16[4,8]{1,0} fusion(%copy.9), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(step)/jvp()/stack/while/body/attention/dot_general" stack_frame_id=3}
  %rem.4 = bf16[4,8]{1,0} fusion(%dot.3), kind=kLoop, calls=%fused_tanh, metadata={op_name="jit(step)/transpose(jvp(stack))/while/body/checkpoint/rematted_computation/mlp/tanh"}
  ROOT %tuple.2 = (s32[], bf16[4,8]{1,0}) tuple(%gte.0, %rem.4)
}

%cond (arg.1: (s32[], bf16[4,8])) -> pred[] {
  %arg.1 = (s32[], bf16[4,8]{1,0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%arg.1), index=0
  %three = s32[] constant(3)
  ROOT %lt.1 = pred[] compare(%gte.2, %three), direction=LT, metadata={op_name="jit(step)/jvp()/stack/while/cond/lt"}
}

ENTRY %main.1 (p: f32[4,8]) -> (s32[], bf16[4,8], f32[4,8]) {
  %p = f32[4,8]{1,0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %zero = s32[] constant(0)
  %convert_fusion = bf16[4,8]{1,0} fusion(%p), kind=kLoop, calls=%fused_convert, metadata={op_name="params[\\'w\\']"}
  %copy-start = (bf16[4,8]{1,0}, bf16[4,8]{1,0}, u32[]) copy-start(%convert_fusion)
  %copy-done = bf16[4,8]{1,0} copy-done(%copy-start)
  %tuple.1 = (s32[], bf16[4,8]{1,0}) tuple(%zero, %copy-done)
  %while.5 = (s32[], bf16[4,8]{1,0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp()/stack/while"}
  %sort.2 = f32[4,8]{1,0} sort(%p), dimensions={1}, to_apply=%compare, metadata={op_name="jit(step)/sort"}
  %update.6 = f32[4,8]{1,0} fusion(%p, %sort.2), kind=kLoop, calls=%fused_add, metadata={op_name="jit(step)/optimizer/add"}
  %gte.7 = s32[] get-tuple-element(%while.5), index=0
  %gte.8 = bf16[4,8]{1,0} get-tuple-element(%while.5), index=1
  ROOT %out = (s32[], bf16[4,8]{1,0}, f32[4,8]{1,0}) tuple(%gte.7, %gte.8, %update.6)
}
"""


@pytest.mark.parametrize("name, want", [
    ("dot.3", ("stack", "attention", "forward")),
    ("rem.4", ("stack", "mlp", "recompute")),
    # a cast of the stacked weights, hoisted out of the loop and named
    # after the parameter, and its prefetch: the loop's
    ("convert_fusion", ("stack", None, "forward")),
    ("copy-start", ("stack", None, "forward")),
    ("copy-done", ("stack", None, "forward")),
    # a copy XLA put in the loop's body: what uses it
    ("copy.9", ("stack", "attention", "forward")),
    ("lt.1", ("stack", None, "forward")),
    # the sort feeds the optimizer: its user's
    ("sort.2", ("optimizer", None, "forward")),
    ("update.6", ("optimizer", None, "forward")),
    ("convert.1", ("stack", None, "forward")),      # inside the fusion
])
def test_an_instruction_with_no_scope_takes_its_users(name, want):
    assert trace.program_scopes(MODULE)[name][1] == want


def test_output_plumbing_with_no_scope_stays_unscoped():
    text = MODULE.replace(
        'metadata={op_name="jit(step)/optimizer/add"}', "")
    assert trace.program_scopes(text)["update.6"][1][0] is None


# -- the benchmark's reader ---------------------------------------------------

TRACED = {"steps": 2, "ops": {
    "dot.3 (fusion)": (0.004, 6),
    "rem.4 (fusion)": (0.003, 6),
    "convert_fusion (fusion)": (0.002, 2),
    "copy-done (copy-done)": (0.001, 2),
    "while.5 (while)": (0.05, 2),               # a container: not counted
    "other_program.1 (fusion)": (0.003, 2)}}     # not the step's


@pytest.mark.parametrize("params, want", [
    ({"top": "stack", "part": "attention"}, 2.0),
    ({"top": "stack", "part": "mlp"}, 1.5),
    ({"top": "stack", "part": None}, 1.5),
    ({"top": "stack"}, 5.0),
    ({"phase": "recompute"}, 1.5),
    ({"top": "head"}, 0.0),
    ({"top": "optimizer"}, 0.0),
])
def test_reader_gives_ms_per_traced_step(recorded, params, want):
    trace.record_program(_Text(MODULE))
    assert reader.read(params, {}, TRACED, {}) == pytest.approx(want)


def test_reader_share_is_the_unscoped_part_and_prints_the_table(
        recorded, capsys):
    text = MODULE.replace(
        'metadata={op_name="jit(step)/optimizer/add"}', "")
    trace.record_program(_Text(text))
    traced = {"steps": 2, "ops": dict(TRACED["ops"], **{
        "update.6 (fusion)": (0.002, 2)})}
    got = reader.read({"share": "unscoped"}, {}, traced, {})
    assert got == pytest.approx(100.0 * 0.002 / 0.012)
    line = [ln for ln in capsys.readouterr().err.splitlines()
            if ln.startswith("run.py: scopes: ")]
    assert len(line) == 1
    table = json.loads(line[0][len("run.py: scopes: "):])
    assert table["step_leaf_ms"] == pytest.approx(6.0)
    assert table["other_programs_ms"] == pytest.approx(1.5)
    assert table["rows_ms"]["stack/attention/forward"] == pytest.approx(2.0)
    assert table["rows_ms"]["-/-/forward"] == pytest.approx(1.0)


@pytest.mark.parametrize("traced, record", [
    (None, True),                                # untraced
    ({"steps": 0, "ops": TRACED["ops"]}, True),  # no whole step traced
    (TRACED, False),                             # no step recorded
])
def test_reader_finds_nothing(recorded, traced, record):
    if record:
        trace.record_program(_Text(MODULE))
    for params in ({"top": "stack"}, {"share": "unscoped"}):
        assert reader.read(params, {}, traced, {}) is None


# -- every stack's step at its cell's rehearsal sizes -------------------------

def _rehearsal(config_name, traffic_name):
    config = load_json("configs", config_name + ".json")
    traffic = load_json("traffic", traffic_name + ".json")
    return (overlay(config, config.get("rehearse", {})),
            overlay(traffic, traffic.get("rehearse", {}))["sequence_length"])


def _gpt(mesh, opt):
    from paddlebox_tpu.models.gpt import (GPTConfig, init_gpt,
                                          make_gpt_train_step)
    c, seq = _rehearsal("gpt2_medium", "train_s1024")
    cfg = GPTConfig(vocab_size=c["vocab_size"], d_model=c["n_embd"],
                    n_heads=c["n_head"], n_layers=c["n_layer"],
                    d_ff=c["n_inner"], max_seq_len=c["n_positions"])
    params, specs = init_gpt(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((c["sequences_per_chip"], seq), jnp.int32)
    return make_gpt_train_step(cfg, mesh, specs, opt), params, (tok, tok)


def _hybrid(mesh, opt):
    from benchmarks.runners.hybrid_train import program_config
    from paddlebox_tpu.models.nemotron_h import (init_nemotron_h,
                                                 make_nemotron_h_train_step)
    c, seq = _rehearsal("nemotron3_super_120b", "train_s8192")
    cfg = program_config(c)
    params, specs = init_nemotron_h(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((c["sequences_per_chip"], seq), jnp.int32)
    return (make_nemotron_h_train_step(cfg, mesh, specs, opt), params,
            (tok, tok))


def _looped(mesh, opt):
    from benchmarks.runners.looped_train import program_config
    from paddlebox_tpu.models.looped import (init_looped,
                                             make_looped_train_step)
    c, seq = _rehearsal("ouro_2_6b", "train_s4096")
    cfg = program_config(c)
    params, specs = init_looped(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((c["sequences_per_chip"], seq), jnp.int32)
    return make_looped_train_step(cfg, mesh, specs, opt), params, (tok, tok)


def _blockdiff(mesh, opt):
    from benchmarks.runners.block_diffusion_train import program_config
    from paddlebox_tpu.models.block_diffusion import (
        init_block_diffusion, make_block_diffusion_train_step)
    c, seq = _rehearsal("sdar_30b_a3b", "train_bd_s4096")
    cfg = program_config(c)
    params, specs = init_block_diffusion(jax.random.PRNGKey(0), cfg)
    b = c["sequences_per_chip"]
    batch = (jnp.zeros((b, seq), jnp.int32),
             jnp.full((b, seq // cfg.block_length), 0.5, jnp.float32),
             jnp.zeros((b, seq), jnp.bool_))
    return (make_block_diffusion_train_step(cfg, mesh, specs, opt), params,
            batch)


@pytest.mark.parametrize("build, parts, recomputes", [
    (_gpt, {"attention", "mlp"}, False),
    (_hybrid, {"attention", "mamba", "moe"}, True),
    (_looped, {"attention", "mlp"}, True),
    (_blockdiff, {"attention", "moe"}, True),
], ids=["gpt", "hybrid", "looped", "blockdiff"])
def test_every_instruction_of_a_stacks_step_has_a_scope(
        recorded, build, parts, recomputes):
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    opt = optax.adafactor(1e-3)
    step, params, batch = build(mesh, opt)
    compiled = step.lower(params, opt.init(params), *batch).compile()
    assert list(recorded) == [compiled]
    text = compiled.as_text()
    scopes = trace.program_scopes(text)
    users = collections.defaultdict(set)
    for line in text.splitlines():
        m = trace._INSTRUCTION.match(line)
        if m:
            for operand in trace._OPERAND.findall(trace._opcode(
                    m.group(2))[1]):
                users[operand].add(m.group(1))
    root = re.search(r"^  ROOT %?([^\s=]+) = ",
                     text[text.index("\nENTRY"):], re.M).group(1)
    leaves = [n for n in _executed(text)
              if scopes[n][0] not in PLUMBING | trace.CONTAINERS]
    # XLA's copies of what the step returns untouched feed nothing but the
    # step's outputs: plumbing too
    unscoped = [n for n in leaves if scopes[n][1][0] is None
                and not (scopes[n][0] == "copy" and users[n] <= {root})]
    assert not unscoped, [(n, scopes[n]) for n in unscoped[:10]]
    found = {scopes[n][1] for n in leaves}
    assert {s[0] for s in found} >= set(trace.SCOPE_TOPS)
    assert {s[1] for s in found if s[0] == "stack"} - {None} == parts
    assert {s[2] for s in found} == (
        {"forward", "backward", "recompute"} if recomputes
        else {"forward", "backward"})
