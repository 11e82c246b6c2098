"""The system under test: the serving program under ``src/``, built the
way ``launch/serve.build_engine`` builds it, from a config file.

This is the one module of the benchmark that imports the program.  It
takes from it the engine, its request type and its model configuration,
and checks that they match what the config file states.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path


def import_program(root: Path):
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.configs as C  # noqa: F401  (fails where there is no program)
    return C


def model_config(root: Path, config: dict, arch):
    """The program's ModelConfig for ``config``: its arch id with the
    stated overrides, checked against the ``model`` block key by key, for
    every key of the architecture module's ``PROGRAM_KEYS``."""
    C = import_program(root)
    cfg = dataclasses.replace(C.get(config["arch"]), **config.get("overrides", {}))
    m = config["model"]
    bad = {}
    for k, attr in arch.PROGRAM_KEYS.items():
        attr, names = (attr, None) if isinstance(attr, str) else attr
        want = names[m[k]] if names else m[k]
        if getattr(cfg, attr) != want:
            bad[k] = (m[k], getattr(cfg, attr))
    if bad:
        raise ValueError(f"config file and program disagree (file, program): {bad}")
    return cfg


def check_layout(cfg, layout: dict) -> None:
    """The program's parameter tree must have exactly the benchmark's
    leaves and shapes, or the benchmark's weights would not fit it."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    program = jax.eval_shape(
        lambda key: M.init_params(cfg, key, jnp.dtype(cfg.dtype)),
        jax.random.PRNGKey(0))
    compare_layout(program, layout)


def _flat(layout: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in layout.items():
        out.update(_flat(v, f"{prefix}{k}/") if isinstance(v, dict)
                   else {f"{prefix}{k}": tuple(v)})
    return out


def compare_layout(program, layout: dict) -> None:
    """``program``, a tree of arrays or shape structs such as the program's
    parameters, must have exactly the leaves and shapes of ``layout``
    (``weights.layout``: nested dicts of shapes)."""
    import jax

    got = {"/".join(str(getattr(k, "key", k)) for k in p): tuple(a.shape)
           for p, a in jax.tree_util.tree_flatten_with_path(program)[0]}
    want = _flat(layout)
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"program parameter layout differs from the "
                         f"benchmark's: {diff}")


def build_engine(cfg, params, config: dict, mix: dict, recorder: bool = False):
    """A ServingEngine as ``launch/serve.build_engine`` makes one: tiered
    kernels, the compiled decode step, FCFS whole-prompt admission, the
    config's offload ratio and page size.  ``params`` goes straight into
    the engine, so nothing else holds the unsplit tree."""
    import jax

    from repro.core.hardware import TPU_V5E, hardware_for
    from repro.obs.trace import ChromeTraceRecorder
    from repro.serving.engine import ServingEngine

    hw = hardware_for(jax.devices()[0]) if jax.default_backend() == "tpu" else TPU_V5E
    return ServingEngine(
        cfg, params, hw=hw, max_batch=int(mix["slots"]),
        max_len=int(mix["max_len"]),
        global_offload_ratio=float(config["offload_ratio"]),
        use_kernels=True, page_size=int(config["page_size"]),
        scheduler="fcfs", jit_step=True,
        recorder=ChromeTraceRecorder() if recorder else None)


def request(rid: int, prompt, max_new_tokens: int):
    from repro.serving.engine import Request

    return Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens)


def busy(engine) -> bool:
    """Work the engine would do on its next step."""
    return (any(r is not None for r in engine.active) or bool(engine.prefilling)
            or bool(engine.scheduler.ready))


def kv_spills(engine) -> int:
    """Pages the paged KV pool has moved from its local to its remote tier
    to make room (the pool's own counter)."""
    return int(engine.pcache.spills) if engine.pcache is not None else 0


def admission_seconds(engine, since: int) -> float | None:
    """Seconds of the engine's own ``admission`` spans recorded after
    event ``since`` (None without a recorder)."""
    events = getattr(engine.recorder, "events", None)
    if events is None:
        return None
    return sum(e["dur"] for e in events[since:]
               if e.get("ph") == "X" and e.get("name") == "admission") / 1e6


def recorder_mark(engine) -> int:
    return len(getattr(engine.recorder, "events", []))


def wrap_decode_step(wrap) -> None:
    """Put ``wrap(step)`` in the place of the program's paged tiered
    decode step, for the engines built after this call (faults.py)."""
    from repro.serving import tiered_decode as TD

    TD.paged_tiered_decode_step = wrap(TD.paged_tiered_decode_step)
