"""The trace -> modules and scopes reduction on a small trace recorded on
the v5e from the program's own train step and fused serving step
(``record_scopes_trace.py`` says how), and the reader of the ``.xplane.pb``
on a file written here."""

import json
import os

import pytest

from chipbench import scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "scopes_small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def red(recorded):
    return scopes.reduce(recorded["events"])


def test_recorded_trace_is_from_a_tpu_and_holds_both_lines(recorded):
    assert recorded["device"].startswith("TPU")
    lines = {(e["plane"], e["line"]) for e in recorded["events"]}
    assert lines == {("/device:TPU:0", "XLA Modules"),
                     ("/device:TPU:0", "XLA Ops")}


def test_modules_are_the_programs_fixed_names(recorded, red):
    assert set(red["modules"]) == {"train_step", "_step_impl",
                                   "_set_slot_impl"}
    line = sorted((e for e in recorded["events"]
                   if e["line"] == "XLA Modules"),
                  key=lambda e: e["start_ns"])
    assert [scopes.module_name(e["name"]) for e in line] == [
        "train_step", "train_step", "_set_slot_impl", "_set_slot_impl",
        "_step_impl", "_step_impl"]
    # whole runs only: the first and the last event of the line may have
    # been cut by the profiler's start and stop
    assert {k: m["count"] for k, m in red["modules"].items()} == {
        "train_step": 1, "_set_slot_impl": 2, "_step_impl": 1}
    assert red["modules"]["train_step"]["seconds"] == pytest.approx(
        line[1]["dur_ns"] / 1e9)
    assert scopes.module_ms(red, "train_step") == pytest.approx(
        line[1]["dur_ns"] / 1e6)
    assert scopes.module_ms(red, "_set_slot_impl") == pytest.approx(
        (line[2]["dur_ns"] + line[3]["dur_ns"]) / 2e6)


def test_every_scope_the_program_names_shows_and_they_add_up(recorded, red):
    assert set(red["scopes"]) == {
        "embed", "attn", "mlp", "head_xent", "grad_norm", "optimizer",
        "attn_proj", "kv_update", "kv_gather", "head_sample",
        "scan_carry", "unscoped"}
    assert all(v > 0 for v in red["scopes"].values())
    assert sum(red["scopes"].values()) == pytest.approx(red["leaf_s"])
    # leaves only: a `while` spans its body on the same line, and the
    # leaves of whole runs add up to no more than the runs themselves
    ops = [e for e in recorded["events"] if e["line"] == "XLA Ops"]
    assert any(e["name"].startswith("%while") for e in ops)
    runs_s = sum(e["dur_ns"] for e in recorded["events"]
                 if e["line"] == "XLA Modules") / 1e9
    assert 0.7 * runs_s < red["leaf_s"] <= runs_s
    assert red["leaf_s"] < 0.75 * sum(e["dur_ns"] for e in ops) / 1e9
    busy = trace.reduce(recorded["events"])["busy_s"]
    shares = [scopes.scope_share(red, s, busy) for s in red["scopes"]]
    # the busy union also holds the loop control between a `while`'s body
    # operations: percents of these microsecond programs, nothing at the
    # cells' sizes (there the shares add up to 100 within 1, PERF.md)
    assert 95.0 < sum(shares) <= 100.0


def test_a_leaf_under_a_while_counts_under_its_scope(recorded):
    ops = [e for e in recorded["events"] if e["line"] == "XLA Ops"]
    leaves = trace._leaves(ops)
    inside = [e for e in leaves if "/while/body/" in (e["path"] or "")]
    assert {scopes.scope_of(e["path"]) for e in inside} >= {
        "attn", "mlp", "kv_gather", "kv_update", "attn_proj"}
    # the backward pass and the rematerialised forward count under the
    # forward scope
    back = [e for e in inside if "transpose(jvp(" in e["path"]]
    assert {scopes.scope_of(e["path"]) for e in back} >= {"attn", "mlp"}
    assert any("rematted_computation" in e["path"] for e in back)


def test_named_pallas_kernels_show_under_their_names(recorded):
    ops = [e for e in recorded["events"] if e["line"] == "XLA Ops"]
    for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        mine = [e for e in ops if e["name"].startswith(f"%{kernel}.")]
        assert mine, kernel
        assert all(f"/attn/{kernel}/pallas_call" in e["path"]
                   and scopes.scope_of(e["path"]) == "attn" for e in mine)


def test_operations_under_no_name_are_counted_and_named(recorded, red):
    leaves = trace._leaves([e for e in recorded["events"]
                            if e["line"] == "XLA Ops"])
    assert any(e["path"] is None for e in leaves)
    top = red["unnamed_top"]
    assert top and len(top) <= trace.TOP
    assert sum(s for _, s, _ in top) <= red["scopes"]["unscoped"] \
        + red["scopes"]["scan_carry"] + 1e-12
    assert all(" = " not in n for n, _, _ in top)
    # what the compiler added (copies of weights) carries no path; what
    # has one names no scope
    assert any(n.startswith(("copy", "slice")) and path is None
               for n, _, path in top)
    assert {scopes.scope_of(path) for _, _, path in top} \
        <= {"unscoped", "scan_carry"}
    # a scan's own work around its body — slicing the stacked weights for
    # a layer, the carry's copies at the loop's level — lies on the
    # `while` and under no scope of the body
    carry = {trace.short_name(e["name"]).split(".")[0].split(" ")[0]
             for e in leaves if scopes.scope_of(e["path"]) == "scan_carry"}
    assert carry >= {"copy", "convert"}, carry
    assert all("/while" in e["path"] for e in leaves
               if scopes.scope_of(e["path"]) == "scan_carry")


@pytest.mark.parametrize("path,scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/attn/mul:", "attn"),
    ("jit(train_step)/transpose(jvp(head_xent))/while/body/mul:",
     "head_xent"),
    ("jit(train_step)/jvp(embed)/gather:", "embed"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/jit(silu):", "mlp"),
    ("jit(_step_impl)/while/body/mlp/moe/dot_general:", "moe"),
    ("jit(_step_impl)/while/body/attn_proj/dot_general:", "attn_proj"),
    ("jit(train_step)/optimizer/grad_normalize/mul:", "optimizer"),
    ("jit(_step_impl)/while/body/attention/mul:", "scan_carry"),
    ("jit(_step_impl)/while:", "scan_carry"),
    ("jit(_step_impl)/while/body/dynamic_update_slice:", "scan_carry"),
    ("jit(train_step)/transpose(jvp())/while/body/squeeze:", "scan_carry"),
    ("jit(_step_impl)/jit(take_along_axis)/gather:", "unscoped"),
    ("state['tables']:", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_scope_is_the_innermost_of_the_programs_names(path, scope):
    assert scopes.scope_of(path) == scope


def test_hand_made_events():
    def ev(line, name, s, d, path=None, plane="/device:TPU:0"):
        return {"plane": plane, "line": line, "name": name, "start_ns": s,
                "dur_ns": d, "path": path}
    events = [
        ev("XLA Modules", "jit__step_impl(123)", -70, 70),    # cut short
        ev("XLA Modules", "jit__step_impl(123)", 0, 100),
        ev("XLA Modules", "jit__step_impl(456)", 200, 60),
        ev("XLA Modules", "jit__set_slot_impl(7)", 300, 1),
        ev("XLA Ops", "%while = () while()", 0, 90, "jit(_step_impl)/while:"),
        ev("XLA Ops", "%fusion.1 = f32[2]{0} fusion()", 0, 40,
           "jit(_step_impl)/while/body/kv_gather/gather:"),
        ev("XLA Ops", "%fusion.2 = f32[2]{0} fusion()", 40, 50,
           "jit(_step_impl)/while/body/attn/mul:"),
        ev("XLA Ops", "%copy.1 = f32[2]{0} copy()", 200, 40),
        ev("XLA Ops", "%fusion.5 = f32[2]{0} fusion()", 240, 20,
           "jit(_step_impl)/while/body/dynamic_update_slice:"),
        ev("XLA Ops", "%fusion.2 = f32[2]{0} fusion()", 0, 10,
           "jit(_step_impl)/while/body/attn/mul:", plane="/device:TPU:1"),
        {"plane": "/host:CPU", "line": "python3", "name": "engine.dispatch",
         "start_ns": 0, "dur_ns": 500},
    ]
    red = scopes.reduce(events)
    # two chips: every number is averaged over them
    assert red["modules"] == {"_step_impl": {"count": 1.0,
                                             "seconds": 80e-9}}
    assert red["scopes"] == pytest.approx(
        {"kv_gather": 20e-9, "attn": 30e-9, "scan_carry": 10e-9,
         "unscoped": 20e-9})
    assert red["unnamed_top"] == [
        ["copy.1 f32[2]", 20e-9, None],
        ["fusion.5 f32[2]", 10e-9,
         "jit(_step_impl)/while/body/dynamic_update_slice:"]]
    assert scopes.module_ms(red, "_step_impl") == pytest.approx(80e-6)
    assert scopes.scope_share(red, "attn", 100e-9) == pytest.approx(30.0)


def test_readers_find_nothing_rather_than_zero(red):
    assert scopes.reduce([]) is None
    assert scopes.reduce([{"plane": "/host:CPU", "line": "python3",
                           "name": "x", "start_ns": 0, "dur_ns": 5}]) is None
    assert scopes.module_ms(red, "no_such_program") is None
    assert scopes.module_ms(None, "train_step") is None
    assert scopes.scope_share(red, "moe", 1.0) is None
    assert scopes.scope_share(None, "attn", 1.0) is None
    assert scopes.scope_share(red, "attn", None) is None
    assert scopes.scope_share(red, "attn", 0.0) is None


# ---- the reader of the .xplane.pb, on a file written here

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """fields: (number, int) -> varint, (number, bytes/str) -> length-
    delimited."""
    out = bytearray()
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return bytes(out)


def _entry(key, value):
    return _msg((1, key), (2, value))


def test_the_xplane_reader_on_a_written_file(tmp_path):
    stat_md = [(5, _entry(7, _msg((1, 7), (2, "tf_op")))),
               (5, _entry(8, _msg((1, 8), (2, "flops")))),
               (5, _entry(9, _msg((1, 9), (2, "jit(f)/attn/exp:"))))]
    event_md = [
        # the path as a string, as a reference to a stat's name, absent
        (4, _entry(1, _msg((1, 1), (2, "%fusion.1 = f32[2]{0} fusion()"),
                           (5, _msg((1, 8), (3, 12))),
                           (5, _msg((1, 7), (5, "jit(f)/mlp/mul:")))))),
        (4, _entry(2, _msg((1, 2), (2, "%exp.2 = f32[2]{0} exp()"),
                           (5, _msg((1, 7), (7, 9)))))),
        (4, _entry(3, _msg((1, 3), (2, "%copy.3 = f32[2]{0} copy()")))),
        (4, _entry(4, _msg((1, 4), (2, "jit_f(99)")))),
    ]
    ops = _msg((2, "XLA Ops"), (3, 5),
               (4, _msg((1, 1), (2, 1000), (3, 2000))),
               (4, _msg((1, 2), (2, 3000), (3, 500))),
               (4, _msg((1, 3), (2, 4000), (3, 0))),       # no duration
               (4, _msg((1, 3), (2, 5000), (3, 250))))
    mods = _msg((2, "XLA Modules"), (3, 5),
                (4, _msg((1, 4), (2, 1000), (3, 4250))))
    other = _msg((2, "Steps"), (3, 5), (4, _msg((1, 4), (2, 0), (3, 9))))
    device = _msg((1, 1), (2, "/device:TPU:0"), (3, ops), (3, mods),
                  (3, other), *event_md, *stat_md)
    host = _msg((1, 2), (2, "/host:CPU"),
                (3, _msg((2, "python3"), (4, _msg((1, 1), (3, 7))))))
    pb = tmp_path / "plugins" / "profile" / "t" / "h.xplane.pb"
    pb.parent.mkdir(parents=True)
    pb.write_bytes(_msg((1, host), (1, device)))
    events = scopes.load_dir(str(tmp_path))
    assert [(e["line"], e["name"][:9], e["start_ns"], e["dur_ns"],
             e.get("path")) for e in events] == [
        ("XLA Ops", "%fusion.1", 6.0, 2.0, "jit(f)/mlp/mul:"),
        ("XLA Ops", "%exp.2 = ", 8.0, 0.5, "jit(f)/attn/exp:"),
        ("XLA Ops", "%copy.3 =", 10.0, 0.25, None),
        ("XLA Modules", "jit_f(99)", 6.0, 4.25, None)]
    red = scopes.reduce(events)
    assert red["modules"] == {}     # one run, and it may have been cut
    assert scopes.module_ms(red, "f") is None
    assert red["scopes"] == pytest.approx(
        {"mlp": 2e-9, "attn": 0.5e-9, "unscoped": 0.25e-9})
    with pytest.raises(FileNotFoundError):
        scopes.load_dir(str(tmp_path / "plugins" / "profile" / "none"))
