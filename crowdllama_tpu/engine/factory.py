"""Runner construction from a Configuration + ServingPlan.

ONE builder shared by the leader engine (engine/engine.py) and the
multi-host follower loop (parallel/replicated.py run_follower): the
leader-replicated dispatch model depends on every process building a
bit-identical runner (same class, same mesh, same pool geometry, same
params), so the branching must not be duplicated in two places that can
drift.  The reference has no analog — its engine is whatever Ollama
process the worker shells out to (/root/reference/pkg/crowdllama/
api.go:108-160).
"""

from __future__ import annotations


def build_runner(config, plan, cfg, params):
    """Instantiate the runner ``plan`` names (unwrapped — the engine adds
    the ReplicatedRunner proxy on the leader itself)."""
    if cfg.is_hybrid:
        # Mamba layers beside attention layers (engine/hybrid.py): two
        # kinds of state, kept by the paged runner's subclass alone, and
        # no rollback for speculation.
        from crowdllama_tpu.engine.hybrid import refuse_speculation

        if plan.spec:
            refuse_speculation(cfg, f"{plan.spec} speculation")
        if plan.kv_layout != "paged":
            raise ValueError(
                f"{cfg.name!r} has layers of several kinds and is served "
                f"on the paged layout only, not {plan.kv_layout!r}")
    kwargs = dict(
        params=params,
        mesh_spec=config.mesh_shape,
        max_slots=config.max_batch_slots,
        max_seq=cfg.max_context_length,
    )
    if plan.kv_layout == "paged":
        kwargs.update(
            page_size=config.kv_page_size,
            pool_tokens=config.kv_pool_tokens,
            prefix_cache=config.kv_prefix_cache,
            kv_dtype=plan.kv_dtype,
            step_token_budget=config.step_token_budget)
        if cfg.is_hybrid:
            from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner

            return HybridPagedModelRunner(cfg, **kwargs)
        if plan.runner == "DraftSpecPagedModelRunner":
            from dataclasses import replace as _replace

            from crowdllama_tpu.engine.spec import DraftSpecPagedModelRunner
            from crowdllama_tpu.engine.weights import (
                is_native_checkpoint,
                load_or_init_params,
                native_config_from_dir,
            )
            from crowdllama_tpu.models.config import get_config

            if (config.spec_draft_path
                    and is_native_checkpoint(config.spec_draft_path)):
                # A distill-draft checkpoint carries its own architecture
                # (2-layer distilled drafts have no registry entry) —
                # --spec-draft-model is optional and ignored for shapes.
                draft_cfg = _replace(
                    native_config_from_dir(config.spec_draft_path),
                    max_context_length=cfg.max_context_length)
            else:
                draft_cfg = get_config(
                    config.spec_draft_model,
                    max_context_length=cfg.max_context_length)
            draft_params = None
            if config.spec_draft_path:
                draft_params = load_or_init_params(
                    draft_cfg, config.spec_draft_path)
            return DraftSpecPagedModelRunner(
                cfg, draft_cfg=draft_cfg, draft_params=draft_params,
                draft_len=config.spec_draft, **kwargs)
        if plan.runner == "SpecPagedModelRunner":
            from crowdllama_tpu.engine.spec import SpecPagedModelRunner

            return SpecPagedModelRunner(
                cfg, draft_len=config.spec_draft, **kwargs)
        from crowdllama_tpu.engine.paged import PagedModelRunner

        return PagedModelRunner(cfg, **kwargs)
    if plan.runner == "SpecModelRunner":
        from crowdllama_tpu.engine.spec import SpecModelRunner

        return SpecModelRunner(cfg, draft_len=config.spec_draft, **kwargs)
    from crowdllama_tpu.engine.runner import ModelRunner

    return ModelRunner(cfg, kv_dtype=plan.kv_dtype, **kwargs)
