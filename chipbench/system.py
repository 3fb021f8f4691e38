"""The system under test, as the benchmark reaches it.

Everything the benchmark takes from the program passes through here: the
model description it builds from the configuration file, the packing of
the benchmark's weights under the configuration's policy, the serving
engine with the cell's settings, and the engine's spans and counters.
This is the one module that imports the program.

What depends on the architecture (the model description, the packing and
the serving shapes the costs count) lives in the module the configuration
names: ``architectures/<architecture>.py`` in the checkout's
``chipbench/`` (``ROOT`` here; a test's checkout gives its own ``root``).
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT.parent / "src"
_modules: dict = {}


def program():
    """The parts of the program the benchmark drives."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.configs.base import ArchConfig
    from repro.core.policy import scheme_policy
    from repro.obs import trace as obs_trace
    from repro.quant import apply as qapply
    from repro.serve import engine
    return ArchConfig, scheme_policy, obs_trace, qapply, engine


def module(path: Path, name: str):
    """The Python file at ``path`` as module ``name`` (whose package part
    its relative imports resolve against), loaded once per process.  A
    name with no file fails here, before anything is set up."""
    if path not in _modules:
        if not path.is_file():
            raise FileNotFoundError(f"{name}: no file {path}")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


def architecture(name: str, root: Path = ROOT):
    """``architectures/<name>.py`` under ``root``."""
    return module(root / "architectures" / f"{name}.py",
                  f"chipbench.architectures.{name}")


def arch(conf: dict, root: Path = ROOT):
    """The program's model description of the configuration."""
    return architecture(conf["architecture"], root).arch(conf)


def pack(conf: dict, seed: int, root: Path = ROOT):
    """The program's serve tree of the benchmark's weights at ``seed``,
    packed under the configuration's policy."""
    return architecture(conf["architecture"], root).pack(conf, seed)


def dims(conf: dict, root: Path = ROOT):
    """The serving shapes the cost functions and metric readers count."""
    return architecture(conf["architecture"], root).dims(conf)


def engine(conf: dict, params, settings: dict, seed: int, root: Path = ROOT):
    """A serving engine with the cell's settings, greedy, 4-bit KV."""
    eng_mod = program()[4]
    s = conf["serving"]
    if tuple(s["kv_bits"]) != (4, 4) or s["kv_block"] != 16:
        raise ValueError("the engine's state_bits takes one width; this "
                         "configuration asks for another KV layout")
    return eng_mod.ServeEngine(
        arch(conf, root), params, max_slots=settings["max_slots"],
        max_seq=settings["max_seq"], prefill_pad=settings["prefill_pad"],
        batch_admission=settings["batch_admission"], qimpl=s["qimpl"],
        state_bits=s["kv_bits"][0], kv_block=s["kv_block"], seed=seed % 2**31)


def counters(eng) -> dict[str, float]:
    """The value of every counter in the engine's metrics registry."""
    from repro.obs.metrics import Counter
    return {name: m.value for name, m in eng.metrics.items()
            if isinstance(m, Counter)}


def request(uid: int, prompt: list[int], max_new: int):
    return program()[4].Request(uid=uid, prompt=prompt, max_new_tokens=max_new)


def tracer():
    """The program's span tracer (``repro.obs.trace``)."""
    return program()[2]
