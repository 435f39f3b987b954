"""The plain references: one file per architecture, found by the name a
configuration gives (``"reference": "<name>"`` in ``configs/<config>.json``).

A reference is the architecture's forward pass and language-model loss in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul runs in
lower precision unless asked otherwise), one layer at a time, with no kernel,
cache or scan, sharing no code with ``deepspeed_tpu/models/``. It reads the
system's parameter LAYOUT, because it is run on the system's own weights.

The protocol, the same for every ``references/<name>.py``:

``COVERS``
    ``{program key: accepted values, or None for any value}``: what of a
    configuration's ``program`` the module implements. Any other key, and any
    other value of a listed key, is refused by that key's name when the
    module is handed out (``load_reference``), before any arithmetic: a configuration
    outside the architecture is never compared, or counted, as if inside.
``logits_at(program, params, tokens, rows, *, fetch) -> np.ndarray``
    float32 logits ``[len(rows), vocab]`` of one token sequence at ``rows``.
``lm_loss(program, params, tokens, *, fetch) -> float``
    mean next-token cross-entropy of ``tokens`` ``[S + 1]`` or ``[N, S + 1]``.
``param_counts(program) -> dict``
    ``matmul_on_token_path`` (the parameters ONE token multiplies through:
    every projection of every layer it visits and the head, tied or not; a
    sparse model counts the experts a token is routed to, not all of them),
    ``total`` (every parameter held), and what else the architecture wants to
    show. ``flops.py`` builds the training FLOPs per token on the first.

``params`` is the parameter tree as the engine holds it, whole: the reference
slices its own stacks (``params["layers"]``, a later architecture's
``params["moe"]``, ...) and calls ``fetch`` on each slice (a dict of leaves)
just before using it. Serving passes the identity; training passes a
``device_put`` to one device, so a state sharded over four chips is brought
over one layer at a time and never gathered at once.
"""

from __future__ import annotations

import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))
PROTOCOL = ("COVERS", "logits_at", "lm_loss", "param_counts")


class Program(dict):
    """A configuration's ``program`` block (the model's keywords as run) that
    remembers which reference its configuration names: the metric readers
    hand ``flops.py`` the program alone."""

    def __init__(self, keywords: dict, reference: str):
        super().__init__(keywords)
        self.reference = reference


def available() -> list:
    return sorted(f[:-3] for f in os.listdir(HERE) if f.endswith(".py") and f != "__init__.py")


def named_reference(config: dict) -> str:
    """The reference a configuration file names; ends the run, with the list
    of those there, where it names none or one that has no file. Imports
    nothing: ``run.py`` asks before jax may be imported."""
    where = f"configs/{config.get('name', '?')}.json"
    name = config.get("reference")
    if not isinstance(name, str) or not name:
        raise SystemExit(f"{where} names no reference (\"reference\": \"<name>\"); "
                         f"there are: {', '.join(available())}")
    if name not in available():
        raise SystemExit(f"{where}: no references/{name}.py; there are: {', '.join(available())}")
    return name


def program_of(config: dict, block: str = "program") -> Program:
    return Program(config[block], named_reference(config))


class NotCovered(NotImplementedError):
    """A program carries a key, or a value, that its reference does not implement."""


def load_reference(program: Program):
    """The reference module of a program made by ``program_of()``, once it is
    seen to export the protocol and to cover every key the program carries."""
    name = getattr(program, "reference", None)
    if name is None:
        raise TypeError("a plain dict names no reference: build the program with "
                        "program_of(<configuration>)")
    module = importlib.import_module(f"{__name__}.{name}")
    missing = [a for a in PROTOCOL if not hasattr(module, a)]
    if missing:
        raise SystemExit(f"references/{name}.py lacks {', '.join(missing)} of the protocol")
    covers = module.COVERS
    for key, value in program.items():
        if key not in covers:
            raise NotCovered(f"reference {name!r} does not cover the program key {key!r} "
                             f"(= {value!r}); it covers: {', '.join(sorted(covers))}")
        if covers[key] is not None and value not in covers[key]:
            raise NotCovered(f"reference {name!r} covers {key!r} only as "
                             f"{list(covers[key])}, not {value!r}")
    return module
