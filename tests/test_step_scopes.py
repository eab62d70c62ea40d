"""Every op of a step program sits under one ``draco_*`` named scope, and
the scopes are labels only (ISSUE 24): for each approach the compiled step
program's text, metadata stripped, equals that of the same program built
with the four new scopes (``draco_pack`` / ``draco_input`` / ``draco_attack``
/ ``draco_health``) switched off, and at least 95 % of its instructions, by
output bytes, carry a scope. LeNet size, on the CPU; the described-chip case
at ResNet-18 width is in tests/test_chip_compile.py, which shares
``scope_report`` / ``strip_metadata`` from here."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from draco_tpu.config import TrainConfig
from draco_tpu.models import input_shape
from draco_tpu.obs import device_attr as da
from draco_tpu.runtime import make_mesh
from draco_tpu.training.step import build_train_setup

NEW_SCOPES = ("draco_pack", "draco_input", "draco_attack", "draco_health")
# instructions that make no bytes of their own
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "while", "call", "conditional"}


def strip_metadata(hlo_text: str) -> str:
    """The program without its labels: the ``metadata={...}`` of every
    instruction, and the stack-frame tables their ids point into."""
    head, sep, rest = hlo_text.partition("\nFileNames\n")
    if sep:
        end = rest.index("\n\n", rest.index("\nStackFrames\n"))
        hlo_text = head + rest[end:]
    return re.sub(r",?\s*\bmetadata=\{[^}]*\}", "", hlo_text)


def _executed_lines(hlo_text: str) -> list:
    """Instruction lines of the computations that run as ops of their own:
    the entry computation and, from there, while bodies and conditions and
    called computations — not the insides of fusions or reducers."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%([\w.\-]+) \(.*\{\s*$", line)
        if m:
            name = "ENTRY" if m.group(1) else m.group(2)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    out, todo, seen = [], ["ENTRY"], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for line in comps[comp]:
            out.append(line)
            if re.search(r"\s(while|call|conditional)\(", line):
                todo += re.findall(
                    r"(?:body|condition|to_apply|branch_computations=\{?"
                    r"|true_computation|false_computation)=?%([\w.\-]+)",
                    line)
    return out


def scope_report(hlo_text: str) -> dict:
    """Output bytes of the instructions that run as ops of their own, by
    scope: {"share": bytes under a draco_* scope / bytes of the instructions
    the PROGRAM wrote (those with a metadata op_name — a named scope can
    reach no other), "bytes": {scope: bytes}, "compiler": bytes of the
    instructions the compiler made itself (no metadata at all: layout
    copies, XLA:CPU's ``wrapped_*`` fusions, the TPU's concatenate turned
    into update-slices), "unscoped": [(bytes, instruction)] of the
    program's own instructions under no scope, largest first}."""
    ops = da.scope_map_from_hlo(hlo_text)["ops"]
    by_scope, unscoped, compiler = {}, [], 0
    for line in _executed_lines(hlo_text):
        m = da._HLO_LINE_RE.match(line)
        if not m or m.group(2) in _FREE:
            continue
        type_text = line.split("=", 1)[1].split(m.group(2) + "(", 1)[0]
        n = da._shape_bytes(type_text)
        if not da._META_RE.search(line):
            compiler += n
            continue
        scope = ops.get(m.group(1), "")
        by_scope[scope] = by_scope.get(scope, 0) + n
        if not scope:
            unscoped.append((n, m.group(1)))
    total = sum(by_scope.values())
    return {"share": 1.0 - by_scope.get("", 0) / total if total else 0.0,
            "bytes": by_scope, "compiler": compiler,
            "unscoped": sorted(unscoped, reverse=True)}


def new_scopes_off(monkeypatch):
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: (contextlib.nullcontext() if name in NEW_SCOPES
                      else real(name)))


APPROACHES = {
    "baseline": dict(approach="baseline", mode="normal", worker_fail=0),
    "maj_vote": dict(approach="maj_vote", group_size=4, worker_fail=1,
                     err_mode="rev_grad"),
    "cyclic": dict(approach="cyclic", worker_fail=1, err_mode="rev_grad",
                   redundancy="simulate"),
    "approx": dict(approach="approx", worker_fail=0, redundancy="shared",
                   code_redundancy=1.5),
}


def _step_text(kw, many=0) -> str:
    cfg = TrainConfig(network="LeNet", dataset="synthetic-cifar10",
                      batch_size=2, num_workers=8, lr=0.01, momentum=0.9,
                      max_steps=3, eval_freq=0, train_dir="",
                      step_guard="on", **kw)
    # one device, like the one-chip cells: no partitioner-made instructions
    setup = build_train_setup(
        cfg, make_mesh(cfg.num_workers, jax.devices()[:1]))
    n, b = cfg.num_workers, cfg.batch_size
    lead = (many,) if many else ()
    x = jnp.zeros(lead + (n, b) + input_shape(cfg.dataset), jnp.float32)
    y = jnp.zeros(lead + (n, b), jnp.int32)
    mask = np.zeros(lead + (n,), bool)
    if many:
        return setup.train_many.lower(setup.state, x, y, mask,
                                      None).compile().as_text()
    return setup.train_step.lower(setup.state, x, y, mask).compile().as_text()


@pytest.mark.parametrize("many", [0, 2], ids=["train_step", "train_many"])
@pytest.mark.parametrize("approach", sorted(APPROACHES))
def test_scopes_label_every_op_and_change_no_instruction(approach, many,
                                                         monkeypatch):
    on = _step_text(APPROACHES[approach], many)
    report = scope_report(on)
    assert report["share"] >= 0.95, (report["bytes"],
                                     report["unscoped"][:10])
    for scope in ("draco_comp", "draco_pack", "draco_health"):
        assert report["bytes"].get(scope, 0) > 0, report["bytes"]
    new_scopes_off(monkeypatch)
    off = _step_text(APPROACHES[approach], many)
    assert not any(s in off for s in NEW_SCOPES)
    assert strip_metadata(on) == strip_metadata(off)
