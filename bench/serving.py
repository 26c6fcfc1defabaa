"""The system under test, built from a configuration file.

The engine is the program's own (``repro.serve``); what this module adds
is the benchmark's side of the interface: the configuration's model
module (``bench/models``) builds the program config and lays the seeded
weights into the program's parameter tree in one jitted call on the
device, and this module makes the engine with the file's settings: one
``Engine`` on one chip, or on N chips a ``MultiEngine`` pool of N
one-chip replicas behind the program's router.
"""
from __future__ import annotations

import jax

from bench.weights import root_key
from repro.serve.engine import Engine
from repro.serve.multi_engine import EngineTier, MultiEngine
from repro.sharding import params as prm
from repro.sharding.axes import single_device_ctx

ENGINE_KEYS = ("max_slots", "max_len", "paged", "page_size", "num_pages",
               "prefill_batch", "min_bucket", "decode_quantum")


def engine_kwargs(conf: dict) -> dict:
    return {k: conf["engine"][k] for k in ENGINE_KEYS if k in conf["engine"]}


def build_server(model, conf: dict, seed: int, devices):
    """An engine over the seed's weights of ``model`` (a module of
    ``bench/models``) on each of ``devices``: the engine alone on one
    chip, a replica pool on several."""
    cfg = model.program_config(conf)
    kw = engine_kwargs(conf)
    build, defs = model.params_fn(cfg, model.model_shape(conf))
    ctxs = [single_device_ctx(d) for d in devices]
    params = jax.jit(build, out_shardings=prm.shardings(defs, ctxs[0]))(
        root_key(seed))
    if len(ctxs) == 1:
        return Engine(cfg, params, ctxs[0], **kw)
    tiers = []
    for i, ctx in enumerate(ctxs):
        p = params if i == 0 else jax.device_put(params,
                                                 prm.shardings(defs, ctx))
        tiers.append(EngineTier(f"chip{i}", Engine(cfg, p, ctx, **kw)))
    return MultiEngine(tiers)


def engines(server) -> list[Engine]:
    return ([t.engine for t in server.tiers] if isinstance(server, MultiEngine)
            else [server])
