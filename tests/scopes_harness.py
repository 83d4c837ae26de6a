"""The train step's named scopes in its compiled HLO.

Helpers for tests/test_scopes.py, and, run as a script, the repl=2 x
shard=2 case on four virtual CPU devices (in a subprocess, so the main
pytest process keeps one device).  The script prints one JSON object: the
scopes the compiled step names, each collective instruction with its kind
and ``op_name``, and the scopes that the lowered module with debug info
stripped names (none).
"""

import json
import os
import re

COLLECTIVE = re.compile(r"\b(all-gather|reduce-scatter|all-reduce)(?:-start)?\(")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instructions(hlo_text: str) -> list[tuple[str, str, str | None]]:
    """(name, rest of the line, op_name or None) of every instruction."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(m.group(2))
            out.append((m.group(1), m.group(2), op.group(1) if op else None))
    return out


def innermost_scope(op_name: str | None) -> str | None:
    from repro import scopes

    pat = "|".join(re.escape(s) for s in scopes.ALL)
    found = re.findall(rf"(?<![\w.])({pat})(?![\w.])", op_name or "")
    return found[-1] if found else None


def lower_step(arch: str, repl: int = 1, shard: int = 1):
    """The lowered train step of ``arch``'s smoke variant, two micro-steps,
    on a repl x shard mesh of the first devices."""
    from repro.configs import get_config, smoke_variant
    from repro.core.mics import (
        MiCSConfig, build_train_step, init_state_shapes, make_batch_shapes,
    )
    from repro.core.topology import MiCSTopology, make_host_mesh
    from repro.models.build import build_model
    from repro.optim.adamw import OptConfig

    cfg = smoke_variant(get_config(arch))
    model = build_model(cfg, tp=1)
    topo = MiCSTopology(make_host_mesh(1, repl, shard, 1))
    step = build_train_step(model, topo, MiCSConfig(micro_steps=2),
                            OptConfig(total_steps=8, warmup_steps=1))
    batch = make_batch_shapes(model, 4 * repl * shard, 32, 2)
    return step.lower(init_state_shapes(model), batch)


def main() -> None:
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", ""))
    from repro import scopes

    lowered = lower_step("bert-10b", repl=2, shard=2)
    text = lowered.compile().as_text()
    ins = instructions(text)
    stripped = lowered.as_text(debug_info=False)
    print(json.dumps({
        "scopes": sorted({s for _, _, op in ins
                          if (s := innermost_scope(op)) is not None}),
        "collectives": [[n, m.group(1), op] for n, rest, op in ins
                        if (m := COLLECTIVE.search(rest))],
        "stripped_names": [s for s in scopes.ALL if s in stripped],
    }))


if __name__ == "__main__":
    main()
