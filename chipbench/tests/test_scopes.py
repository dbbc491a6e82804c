"""The by-scope reduction (``chipbench/scopes.py``): run with

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_scopes.py -q

Its arithmetic on a sample cut from one real TPU trace
(``data/scope_events.json``: two whole steps of the 13×256 train step
on a v5e, every instruction those steps ran with the fused
computations behind it, the benchmark's spans over them), the
protobuf wire reader on
a hand-made message, and a CPU rehearsal of every scope reader
through ``run.py`` on a fixture manifest of its own. Nothing here is
a device number: the sample's times are data to add up.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from chipbench import scopes  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures",
                       "BENCHMARK.scopes.fixture.json")


@pytest.fixture(scope="module")
def sample():
    with open(os.path.join(HERE, "data", "scope_events.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program(sample):
    (name, prog), = sample["programs"].items()
    assert name.startswith("jit_train_step(")
    return prog


# ------------------------------------------------------- the names

@pytest.mark.parametrize("op_name, scope, cls", [
    ("jit(train_step)/jvp(PolicyNet)/trunk/conv3/conv_general_dilated",
     "jvp(PolicyNet)/trunk/conv3", "fwd"),
    ("jit(train_step)/transpose(jvp(PolicyNet))/trunk/conv3/"
     "conv_general_dilated",
     "transpose(jvp(PolicyNet))/trunk/conv3", "bwd"),
    ("jit(train_step)/train.update/add", "train.update", "update"),
    ("jit(train_step)/train.augment/vmap(jit(rot90))/rev",
     "train.augment/vmap(jit(rot90))", "augment"),
    ("jit(train_step)/jvp(train.loss)/reduce_sum", "jvp(train.loss)",
     "loss"),
    ("jit(train_step)/transpose(jvp(train.loss))/mul",
     "transpose(jvp(train.loss))", "loss"),
    ("jit(train_step)/mul", scopes.UNSCOPED, "unscoped"),
    ("jit(train_step)/jit(_threefry_split)/while/body/add",
     "jit(_threefry_split)/while/body", "unscoped"),
    ("", scopes.UNSCOPED, "unscoped"),
])
def test_scope_and_class_of_an_op_name(op_name, scope, cls):
    assert scopes.scope_of(op_name) == scope
    assert scopes.train_class(scope) == cls


def test_an_event_name_gives_its_instruction():
    line = ("%multiply_add_fusion.4 = f32[3,3,256,256]{3,2,1,0:T(8,128)"
            "S(1)} fusion(f32[3,3,256,256]{3,2,1,0} %copy-done.78), "
            "kind=kOutput, calls=%fused_computation.141")
    assert scopes._instruction(line) == "multiply_add_fusion.4"
    assert scopes._instruction("fusion.7") == "fusion.7"    # CPU


# ------------------------------------------------- the fusion rule

def test_weight_gradient_fusions_belong_to_the_backward_pass(program):
    """The 3×3 weight gradients are fused with the SGD update: their
    ROOT is ``train.update/add``, the convolution inside them is the
    backward pass's, and the convolution wins."""
    resolved = scopes.resolve(program)
    weight_grads = []
    for name, (opcode, _, called) in program["instructions"].items():
        if opcode != "fusion" or not called:
            continue
        root = program["computations"][called[0]][0]
        if "train.update" in program["instructions"][root][1]:
            inside = [program["instructions"][i]
                      for c in called
                      for i in program["computations"][c][1]]
            if any(op == "convolution" for op, _, _ in inside):
                weight_grads.append(name)
    assert len(weight_grads) >= 11, weight_grads
    layers = set()
    for name in weight_grads:
        scope = scopes.scope_of(resolved[name])
        assert scope.startswith("transpose(jvp(PolicyNet))/"), \
            (name, resolved[name])
        assert scopes.train_class(scope) == "bwd"
        layers.add(scope)
    assert {f"transpose(jvp(PolicyNet))/trunk/conv{i}"
            for i in range(2, 13)} <= layers
    assert not any(scopes.train_class(scopes.scope_of(resolved[n]))
                   == "update" for n in weight_grads)


def test_a_root_the_compiler_made_takes_the_scope_inside(program):
    """``convert_reduce_fusion.N`` packs the ReLU mask the forward
    pass saves: its root (a reduce of shifted bits) has no metadata,
    the compare inside it is the forward pass's."""
    resolved = scopes.resolve(program)
    packs = [n for n, (_, own, _) in program["instructions"].items()
             if n.startswith("convert_reduce_fusion") and not own]
    assert len(packs) >= 11, packs      # one per 3×3 layer
    for name in packs:
        assert scopes.train_class(
            scopes.scope_of(resolved[name])) == "fwd", resolved[name]


def test_plain_instructions_keep_their_own_metadata(program):
    resolved = scopes.resolve(program)
    for name, (opcode, op_name, _) in program["instructions"].items():
        if opcode != "fusion":
            assert resolved[name] == op_name


# ----------------------------------------------------- the reduction

def test_the_account_of_the_recorded_steps_is_whole(sample):
    acct = scopes.reduce(sample)
    steps = sample["steps"]
    ops = sample["device"]["/device:TPU:0"]["ops"]
    # no operation of a train step nests in another: self = duration
    total = sum(d for _, _, _, d in ops) / 1e9
    assert acct["busy_s"] == pytest.approx(total, rel=1e-9)
    assert sum(acct["by_scope"].values()) == pytest.approx(
        acct["busy_s"], rel=1e-9)
    split = dict.fromkeys(
        ("fwd", "bwd", "augment", "loss", "update", "unscoped"), 0.0)
    for scope, t in acct["by_scope"].items():
        split[scopes.train_class(scope)] += t
    # the four times, the loss and the unscoped add up to the step
    assert sum(split.values()) == pytest.approx(acct["busy_s"],
                                                rel=1e-9)
    assert split["unscoped"] / acct["busy_s"] < 0.02
    assert 1.5 < split["bwd"] / split["fwd"] < 2.5
    # the update is fused away into the weight gradients
    assert split["update"] < 0.001 * acct["busy_s"]
    per_step = {k: 1e3 * v / steps for k, v in split.items()}
    for cls, want in sample["expect_ms_per_step"].items():
        assert per_step[cls] == pytest.approx(want, rel=1e-6), cls
    assert acct["program_runs"] == {
        next(iter(sample["programs"])): steps}
    assert scopes.has_scope(acct, "train.update")
    assert not scopes.has_scope(acct, "ply.encode")


def test_each_conv_layer_is_accounted_forward_and_backward(sample):
    acct = scopes.reduce(sample)
    for i in range(1, 13):
        fwd = acct["by_scope"][f"jvp(PolicyNet)/trunk/conv{i}"]
        bwd = acct["by_scope"][
            f"transpose(jvp(PolicyNet))/trunk/conv{i}"]
        assert fwd > 0 and bwd > 0
        if i > 1:           # input + weight gradient ~ 2 × forward
            assert 1.4 < bwd / fwd < 2.6, (i, fwd, bwd)


def test_idle_gaps_go_to_the_innermost_span(sample):
    acct = scopes.reduce(sample)
    idle = dict(acct["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(
        acct["window_s"] - acct["busy_s"], rel=1e-6)
    assert set(idle) <= {s[0] for s in sample["spans"]} | {"unlabelled"}
    # the sample's one gap — the host's sync after the last step —
    # sits under chipbench.block inside chipbench.window: the window
    # span never labels, the innermost does
    assert list(idle) == ["chipbench.block"]


def test_a_program_without_metadata_is_all_unscoped():
    events = {
        "device": {"/device:TPU:0": {
            "ops": [["jit_f(1)", "fusion.1", 0.0, 60.0],
                    ["jit_f(1)", "while.2", 100.0, 100.0],
                    ["jit_f(1)", "fusion.3", 120.0, 30.0]],
            "modules": [["jit_f(1)", 0.0, 200.0]]}},
        "spans": [["chipbench.window", 0.0, 400.0],
                  ["rocalphago.sl.step", 210.0, 150.0]],
        "programs": {}}
    acct = scopes.reduce(events)
    assert acct["by_scope"] == {scopes.UNSCOPED: pytest.approx(160e-9)}
    assert acct["busy_s"] == pytest.approx(160e-9)   # nested once
    assert acct["scope_names"] == [scopes.UNSCOPED] or \
        acct["scope_names"] == []
    assert dict(acct["idle_by_span"])["rocalphago.sl.step"] == \
        pytest.approx(200e-9)
    assert not scopes.has_scope(acct, "train.update")


# ------------------------------------------------- the protobuf wire

def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_programs_are_read_off_the_wire(tmp_path):
    """A hand-made ``XSpace``: one ``/host:metadata`` plane whose
    event metadata holds an ``HloProto`` with a fusion calling a
    computation — field numbers as in xplane.proto / hlo.proto."""
    def instruction(name, opcode, op_name, iid, called=()):
        body = _field(1, name) + _field(2, opcode) + _field(35, iid)
        if op_name:
            body += _field(7, _field(2, op_name))
        if called:      # packed repeated int64
            body += _field(38, b"".join(_varint(c) for c in called))
        return body

    fused = (_field(1, "fused_computation") + _field(5, 7)
             + _field(2, instruction("conv.1", "convolution",
                                     "jit(f)/transpose(jvp(Net))/conv1/"
                                     "conv_general_dilated", 11))
             + _field(2, instruction("add.2", "add",
                                     "jit(f)/train.update/add", 12))
             + _field(6, 12))
    entry = (_field(1, "main") + _field(5, 8)
             + _field(2, instruction("fusion.3", "fusion", "", 13,
                                     called=(7,)))
             + _field(2, instruction("mul.4", "multiply",
                                     "jit(f)/mul", 300))
             + _field(6, 13))
    module = _field(1, "jit_f") + _field(3, fused) + _field(3, entry)
    meta = (_field(1, 5) + _field(2, "jit_f(5)")
            + _field(5, _field(1, 1) + _field(6, _field(1, module))))
    plane = (_field(2, "/host:metadata")
             + _field(4, _field(1, 5) + _field(2, meta)))
    other = _field(2, "/host:CPU") + _field(4, _field(1, 1))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, other) + _field(1, plane))

    programs = scopes.read_programs(str(path))
    assert list(programs) == ["jit_f(5)"]
    prog = programs["jit_f(5)"]
    assert prog["instructions"]["fusion.3"] == [
        "fusion", "", ["fused_computation"]]
    assert prog["computations"]["fused_computation"] == [
        "add.2", ["conv.1", "add.2"]]
    resolved = scopes.resolve(prog)
    assert scopes.scope_of(resolved["fusion.3"]) == \
        "transpose(jvp(Net))/conv1"
    assert scopes.scope_of(resolved["mul.4"]) == scopes.UNSCOPED


# -------------------------------------------------------- rehearsals

def test_the_scope_fixture_manifest_is_well_formed():
    from test_chipbench import test_manifest_names_units_and_files

    test_manifest_names_units_and_files(FIXTURE)


@pytest.mark.parametrize("cell", ["toy9x16.train", "toy9x16.selfplay",
                                  "toy9x16.serve"])
def test_scope_readers_rehearse_on_cpu(cell):
    """Every by-scope reader through ``run.py --trace 1``, as the
    chip runs it, on the fixture cells: each prints a number, from
    the same ``/host:metadata`` plane the TPU's trace has."""
    from test_chipbench import load, run_cell

    done = run_cell(FIXTURE, cell, 1)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()
             if ln.startswith("{")]
    line = lines[-1]
    assert line["correct"] is True, done.stdout[-3000:]
    want = {e["name"]: e["unit"] for e in load(FIXTURE)["per_layer"]
            if cell in e["workloads"]}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(isinstance(v["value"], (int, float))
               for v in line["metrics"].values())
    acct = next(ln["scope_account"] for ln in lines
                if "scope_account" in ln)
    assert 0 < acct["busy_s"] <= acct["window_s"]
    with open(os.path.join(BENCH, "out", cell, "scopes.json")) as f:
        whole = json.load(f)
    # XLA:CPU's threads overlap, so self times need not add up to
    # the union here as they do on the chip's one line (the sample)
    assert 0.5 * whole["busy_s"] < sum(whole["by_scope"].values()) \
        < 8 * whole["busy_s"]
    if cell == "toy9x16.serve":
        assert any(name.startswith("rocalphago.session.")
                   for name, _ in whole["idle_by_span"])
