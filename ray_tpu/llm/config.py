"""LLMConfig — the single config object for serve + batch LLM stacks.

(reference: llm/_internal/serve/core/configs/llm_config.py LLMConfig —
model_loading_config, engine_kwargs (forwarded to vLLM at
vllm_models.py:215,219), accelerator_type, deployment_config. Here
engine_kwargs drive the TPU engine instead of vLLM.)
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class ModelLoadingConfig:
    model_id: str = "tiny"  # a size key of the chosen model family
    # checkpoint directory (orbax/npz) or None → random init of `model_cfg`
    model_source: str | None = None
    tokenizer: str | None = "byte"


@dataclass
class LoraConfig:
    """(reference: llm/_internal/serve/core/configs/llm_config.py
    LoraConfig — dynamic_lora_loading_path + max_num_adapters_per_replica;
    adapters load on demand when a request's `model` names one.)"""

    dynamic_lora_loading_path: str = ""  # dir of <adapter_id>.npz files
    max_num_adapters_per_replica: int = 4
    lora_rank: int = 8


@dataclass
class PDConfig:
    """Prefill/decode disaggregation knobs (ray_tpu/llm/pd.py).

    (reference: serving_patterns/prefill_decode/pd_server.py — the proxy
    composes separately-sized prefill and decode pools; kv transfer config
    picks the handoff transport. Here the transport is the paged-KV shm
    plane — ray_tpu/llm/kv_transfer.py.)"""

    # KV handoff granularity in tokens; must divide the engine buckets, so
    # the prefill servers bump min_bucket up to it. Power of two.
    page_size: int = 64
    # per-page shm handoff timeout: a decode replica that never pulls (or
    # dies mid-pull) frees the prefill side's channel after this long
    transfer_timeout_s: float = 60.0
    # pages per transfer message — the in-flight prefetch window. >1
    # amortizes the seqlock handshake + pickle framing over several pages
    # at the cost of prefetch_depth*page_bytes of channel buffer per
    # in-flight transfer
    prefetch_depth: int = 2
    # route decode-side pulls through the shared BatchedKVPuller (one
    # polling thread for ALL in-flight transfers) + streamed slot
    # admission (pages adopted as they arrive). False restores the
    # pull-everything-then-admit path (debug/A-B escape hatch).
    batched_pull: bool = True
    # prefill-tier admission batching (pd.py PrefillCoalescer): concurrent
    # same-bucket prompts coalesce into ONE [B, T] prefill forward. The
    # window is how long the batch leader waits for stragglers; 0 batches
    # only what is already queued.
    prefill_batch_max: int = 4
    prefill_batch_window_s: float = 0.0015
    num_prefill_replicas: int = 1
    num_decode_replicas: int = 1


@dataclass
class LLMConfig:
    model_loading_config: ModelLoadingConfig = field(default_factory=ModelLoadingConfig)
    # TransformerConfig kwargs for the built-in families (gpt2/llama/mixtral/
    # kimi_vl/mellum/ouro/granite/trinity/solar_open2)
    model_family: str = "llama"
    model_kwargs: dict = field(default_factory=dict)
    engine_kwargs: dict = field(default_factory=dict)  # TPUEngine keywords:
                                                       # max_slots, max_len, page_size, ...
    deployment_config: dict = field(default_factory=dict)  # serve options
    # "TPU": every process hosting this config's engine is bound to a chip
    # (replica_actor_options) and the engine refuses to start on any other
    # backend. None: host-only — the engine computes on the CPU (tests).
    accelerator_type: str | None = "TPU"
    lora_config: LoraConfig | None = None
    # PD disaggregation (build_pd_openai_app); None → PDConfig() defaults
    pd_config: PDConfig | None = None

    def replica_actor_options(self) -> dict:
        """Actor options of a process that hosts this config's engine (serve
        replica, PD prefill/decode server, batch worker):
        `deployment_config["ray_actor_options"]`, with a chip request when
        `accelerator_type` is "TPU" — `num_tpus` as given there (4 for a
        tensor-parallel mesh), else one."""
        opts = dict(self.deployment_config.get("ray_actor_options") or {})
        if self.accelerator_type == "TPU":
            if not opts.setdefault("num_tpus", 1):
                raise ValueError(
                    "accelerator_type='TPU' needs a chip: ray_actor_options "
                    f"num_tpus={opts['num_tpus']!r} binds none (use "
                    "accelerator_type=None for a host-only engine)")
        elif self.accelerator_type is not None:
            raise ValueError(
                f"accelerator_type must be 'TPU' or None, got "
                f"{self.accelerator_type!r}")
        return opts

    def build_model(self):
        """Returns (TransformerConfig, params). Cited families live in
        ray_tpu/models; random init unless model_source points at a checkpoint."""
        import jax

        from ray_tpu import models

        factory = {"llama": models.llama_config, "gpt2": models.gpt2_config,
                   "mixtral": models.mixtral_config,
                   "kimi_vl": models.kimi_vl_config,
                   "mellum": models.mellum_config,
                   "ouro": models.ouro_config,
                   "granite": models.granite_config,
                   "trinity": models.trinity_config,
                   "solar_open2": models.solar_open2_config}[self.model_family]
        cfg = factory(self.model_loading_config.model_id, **self.model_kwargs)
        src = self.model_loading_config.model_source
        if src:
            from ray_tpu.llm import checkpoint_io

            params = checkpoint_io.load_params(src)
        else:
            params = models.transformer.init(jax.random.PRNGKey(0), cfg)
        return cfg, params
