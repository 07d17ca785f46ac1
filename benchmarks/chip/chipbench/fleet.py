"""The system under test, built from a configuration file.

This is the one module that imports the program (`repro`, from the
checkout's `src/`): it turns the file's models into `ModelConfig`s,
hands each engine the benchmark's weights in the program's layout, and
builds a `PerLLMServer` over paged `ServingEngine`s, one per replica,
each on the device its replica names, behind a policy from the registry.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.model import Shape
from chipbench.spec import CHECKOUT

sys.path.insert(0, str(CHECKOUT / "src"))

from repro.cluster.server import ServerSpec  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.core import make_policy  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402
from repro.serving.perllm_server import PerLLMServer  # noqa: E402


def model_shapes(config: Dict[str, Any]) -> Dict[str, Shape]:
    return {name: Shape.from_config(name, m["config"])
            for name, m in config["models"].items()}


def program_config(m: Shape, arch_id: str, source: str) -> ModelConfig:
    return ModelConfig(
        arch_id=arch_id, family="dense", citation=source,
        n_layers=m.n_layers, d_model=m.d_model, n_heads=m.n_heads,
        n_kv_heads=m.n_kv_heads, head_dim=m.head_dim, d_ff=m.d_ff,
        vocab_size=m.vocab, activation="swiglu", norm_eps=m.norm_eps,
        tie_embeddings=False, rope_theta=m.rope_theta, dtype=m.dtype)


@functools.partial(jax.jit, static_argnames="m")
def _program_params(key, m: Shape):
    """The benchmark's weights in the program's parameter tree, made on
    the key's device in one call. Norm scales are stored as offsets from
    1 (`x * (1 + scale)`), so zeros are weights of 1."""
    w = W.gen_all(key, m)
    lay = w["layers"]

    def zeros(*shape):
        return jnp.zeros(shape, jnp.float32)

    layer = {"norm1": {"scale": zeros(m.n_layers, m.d_model)},
             "mix": {k: lay[k] for k in ("wq", "wk", "wv", "wo")},
             "norm2": {"scale": zeros(m.n_layers, m.d_model)},
             "mlp": {k: lay[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": w["embed"], "unembed": w["unembed"],
            "final_norm": {"scale": zeros(m.d_model)},
            "stack": {"head": (), "sb": (layer,), "tail": ()}}


def make_params(seed: int, m: Shape, cfg: ModelConfig, device):
    key = jax.device_put(W.model_key(seed, m.name), device)
    want = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    got = jax.eval_shape(lambda: _program_params(key, m))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got), strict=True)):
        raise RuntimeError("the program's parameter tree is not the one "
                           "the benchmark builds; the benchmark needs a "
                           "new adapter")
    return _program_params(key, m)


@dataclasses.dataclass
class Fleet:
    server: PerLLMServer
    engines: List[ServingEngine]
    shapes: Dict[str, Shape]          # model name -> shapes
    replica_model: List[str]          # engine j -> model name
    devices: List[Any]                # engine j -> device


def build(config: Dict[str, Any], seed: int, devices=None) -> Fleet:
    """The configuration's fleet, with weights from `seed`. `devices`
    overrides where replicas go (tests); otherwise replica j goes to
    `jax.devices()[replica["device"]]`."""
    shapes = model_shapes(config)
    cfgs = {name: program_config(shapes[name], m["arch_id"], m["source"])
            for name, m in config["models"].items()}
    all_devs = jax.devices()
    params, engines, specs, names, devs = {}, [], [], [], []
    for j, rep in enumerate(config["replicas"]):
        dev = devices[j] if devices is not None else all_devs[rep["device"]]
        name = rep["model"]
        if (name, dev) not in params:
            params[name, dev] = make_params(seed, shapes[name], cfgs[name],
                                            dev)
        engines.append(ServingEngine(
            cfgs[name], params[name, dev], max_batch=rep["lanes"],
            max_seq=rep["max_seq"], paged=True, kv_blocks=rep["kv_blocks"],
            kv_block_tokens=rep["kv_block_tokens"], temperature=0.0,
            device=dev))
        specs.append(ServerSpec(**rep["server_spec"]))
        names.append(name)
        devs.append(dev)
    jax.block_until_ready([e.params for e in engines])
    server = PerLLMServer(specs, engines,
                          scheduler=make_policy(config["policy"],
                                                len(specs)))
    return Fleet(server=server, engines=engines, shapes=shapes,
                 replica_model=names, devices=devs)
