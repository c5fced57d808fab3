"""Driver of the DeepSeek-V2 (MLA + MoE) serving cells: the open loop,
window, drain and sampling of ``drivers/serve.py``, around the program's
``ServingEngine`` with its latent page pool and the expert layer at the
chip's share of the experts.

Set-up builds the program's model config from the Hugging Face style
configuration first (a program that cannot run it fails there, before
any work), makes the weights from the seed
(``mla_moe_reference.make_params``), builds the engine, and starts the
arrivals ``warm_s`` seconds before the window, as ``serve.py`` does.

The check holds each served token of the sampled requests to the
float32 reference of this share (``mla_moe_reference.served_gaps``): the
widest gap by which a served token's reference logit lies below the
reference's best. The control puts, at each of those positions, the
token that the float8 forward puts first in the served token's place.
"""
from __future__ import annotations

import gc
import time
from pathlib import Path
from typing import Any, Dict, List

import harness
import mla_moe_reference
import numpy as np
import traffic as traffic_gen
from harness import Check, Window

serve = harness.load_module(Path(__file__).resolve().parent / "serve.py")
window, finish = serve.window, serve.finish


def program_config(cfg: Dict[str, Any], name: str):
    """The program's model config for the Hugging Face style ``cfg``;
    refuses what the program cannot run as the config states."""
    import jax.numpy as jnp

    from repro.models.common import Yarn
    from repro.models.transformer import TransformerConfig
    rs = cfg["rope_scaling"]
    if cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is 1e-6")
    if cfg["hidden_act"] != "silu" or cfg["attention_bias"] \
            or cfg["tie_word_embeddings"] or cfg["q_lora_rank"]:
        raise ValueError("the program runs SiLU experts, unbiased "
                         "attention, no q_lora and an untied head here")
    if (cfg["scoring_func"], cfg["topk_method"], cfg["n_group"],
            cfg["moe_layer_freq"], rs["type"]) != ("softmax", "greedy", 1,
                                                   1, "yarn"):
        raise ValueError("the program routes by a greedy softmax top-k on "
                         "every layer after the dense ones, with YaRN rope")
    yarn = Yarn(factor=float(rs["factor"]),
                original_max_position=int(
                    rs["original_max_position_embeddings"]),
                beta_fast=float(rs["beta_fast"]),
                beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
                mscale_all_dim=float(rs["mscale_all_dim"]))
    return TransformerConfig(
        name=name, n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), moe=True,
        n_experts=cfg["published"]["n_routed_experts"],
        n_shared=cfg["n_shared_experts"], top_k=cfg["num_experts_per_tok"],
        d_ff_expert=cfg["moe_intermediate_size"],
        n_dense_layers=cfg["first_k_dense_replace"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        experts_held=(cfg["experts_held_first"], cfg["n_routed_experts"]),
        mla=True, kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=0,
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], yarn=yarn,
        dtype={"bfloat16": jnp.bfloat16}[cfg["torch_dtype"]], remat=False)


def setup(cell, seed: int, seconds: float, devices, *, log) -> serve.State:
    pcfg = program_config(cell.config, cell.config_name)
    import jax
    from jax.sharding import Mesh

    from repro.launch.steps import rules_for
    from repro.serving import EngineConfig, ServingEngine
    cfg, mix = cell.config, cell.traffic
    mesh = Mesh(np.asarray(devices).reshape(len(devices)), ("data",))
    rules = rules_for("lm", mesh.axis_names, profile="2d")
    t = time.perf_counter()
    params = mla_moe_reference.make_params(cfg, seed)
    jax.block_until_ready(params)
    log(f"[serve] weights {time.perf_counter() - t:.3f} s")
    slots, page = int(mix["slots"]), int(mix["page_size"])
    per_req = int(mix["pages_per_slot"])
    ecfg = EngineConfig(n_slots=slots, page_size=page,
                        n_pages=slots * per_req, max_pages_per_req=per_req,
                        temperature=0.0, seed=seed & 0x7FFFFFFF,
                        replace_every=int(mix["replace_every"]))
    with mesh:
        engine = ServingEngine(params, pcfg, rules, ecfg)
    phases = (float(mix["warm_s"]), float(seconds),
              float(mix["drain_s"]) + 5.0)
    schedule = traffic_gen.open_loop(mix, seed, phases, cfg["vocab_size"])
    st = serve.State(cell=cell, seed=seed, params=params, engine=engine,
                     mesh=mesh, schedule=schedule,
                     t_zero=time.perf_counter())
    serve._serve_until(st, float(mix["warm_s"]), count=False)
    log(f"[serve] warm: {len(st.tracked)} requests arrived, "
        f"{len(st.engine.scheduler.active)} active, "
        f"{len(st.engine.scheduler.queue)} queued")
    return st


def free_program_state(st: serve.State) -> None:
    st.engine.cache.latent_pool = None
    st.engine = None
    gc.collect()


def _take_sample(st: serve.State) -> List[Any]:
    """The requests to compare, drawn once; the program's state is freed
    then, so that the reference has the chip's memory."""
    if st.sample is None:
        st.sample = serve._sample(st)
        free_program_state(st)
    return st.sample


def check(st: serve.State, win: Window, *, log) -> List[Check]:
    reqs = _take_sample(st)
    log(f"[serve] window {win.seconds:.3f} s: {win.attempted} requests due, "
        f"{win.context['first_tokens']} with a first token at the close, "
        f"{win.context.get('waiting', 0)} still waiting after a drain of "
        f"{win.context.get('drain_s', 0.0):.3f} s, "
        f"{win.context['itl_n']} token gaps, {win.context['steps']} steps, "
        f"queue {win.context['queued'][0]} -> {win.context['queued'][1]}; "
        f"checking {len(reqs)} finished requests")
    limit = float(st.cell.limits["logit_gap"])
    if not reqs:
        return [Check("logit_gap", float("inf"), limit)]
    g = np.concatenate([mla_moe_reference.served_gaps(
        st.params, st.cell.config, r.prompt, r.generated, serve._length(st),
        st.compared.get(r.rid)) for r in reqs])
    log(f"[serve] {len(g)} served tokens compared")
    return [Check("logit_gap", float(g.max()), limit)]


def trace_context(st: serve.State, win: Window) -> Dict[str, Any]:
    return {"model": st.cell.config,
            "page_size": int(st.cell.traffic["page_size"])}


def control(st: serve.State) -> None:
    """Put the control's answer in the program's place: at each position
    of the requests the check compares, the token that the float8
    forward puts first."""
    st.compared = {r.rid: mla_moe_reference.control_tokens(
        st.params, st.cell.config, r.prompt, r.generated, serve._length(st))
        for r in _take_sample(st)}
