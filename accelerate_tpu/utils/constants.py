"""Constants for accelerate_tpu.

TPU-native analogue of the reference constants module
(ref: src/accelerate/utils/constants.py:20-72): checkpoint filenames, env-var
names, mesh axis names. NCCL/torchrun-specific constants are replaced by the
JAX coordinator protocol.
"""

# --- checkpoint file naming -------------------------------------------------
MODEL_NAME = "model"
OPTIMIZER_NAME = "optimizer"
SCHEDULER_NAME = "scheduler"
SAMPLER_NAME = "sampler"
RNG_STATE_NAME = "random_states"
PARAMS_INDEX_NAME = "params_index.json"
SAFE_WEIGHTS_NAME = "model.safetensors"
SAFE_WEIGHTS_INDEX_NAME = "model.safetensors.index.json"
WEIGHTS_NAME = "pytorch_model.bin"  # torch-ecosystem import (ref constants.py:16)
WEIGHTS_INDEX_NAME = "pytorch_model.bin.index.json"
CHECKPOINT_DIR_PREFIX = "checkpoint"

# --- env-var protocol (ACCELERATE_*-style, ref utils/launch.py:76-400) ------
ENV_PREFIX = "ACCELERATE_TPU_"
ENV_COORDINATOR = ENV_PREFIX + "COORDINATOR"          # host:port of process 0
ENV_NUM_PROCESSES = ENV_PREFIX + "NUM_PROCESSES"      # world size (hosts)
ENV_PROCESS_ID = ENV_PREFIX + "PROCESS_ID"            # this host's rank
ENV_MIXED_PRECISION = ENV_PREFIX + "MIXED_PRECISION"
ENV_GRAD_ACCUM_STEPS = ENV_PREFIX + "GRADIENT_ACCUMULATION_STEPS"
ENV_MESH_SHAPE = ENV_PREFIX + "MESH_SHAPE"            # e.g. "data=8,model=4"
ENV_DEBUG_MODE = ENV_PREFIX + "DEBUG"                 # collective shape checks
ENV_CPU = ENV_PREFIX + "USE_CPU"
ENV_FORCE_HOST_DEVICES = ENV_PREFIX + "HOST_DEVICE_COUNT"  # virtual CPU devices
# engine/plugin selection (serialized by `accelerate-tpu config`/`launch`,
# resolved to plugins in Accelerator.__init__ — a saved yaml is launch-ready)
# persistent XLA compilation cache (utils/environment.py
# configure_compilation_cache, wired at PartialState init): dir override, or
# 0/off/false to disable; threshold overrides forward to the jax knobs
ENV_COMPILATION_CACHE = ENV_PREFIX + "COMPILATION_CACHE"
ENV_COMPILATION_CACHE_MIN_COMPILE_SECS = (
    ENV_PREFIX + "COMPILATION_CACHE_MIN_COMPILE_SECS"
)
ENV_COMPILATION_CACHE_MIN_ENTRY_BYTES = (
    ENV_PREFIX + "COMPILATION_CACHE_MIN_ENTRY_BYTES"
)
ENV_ZERO_STAGE = ENV_PREFIX + "ZERO_STAGE"            # 0-3 -> DeepSpeedPlugin
ENV_FSDP_STRATEGY = ENV_PREFIX + "FSDP_SHARDING_STRATEGY"  # FULL_SHARD|...
ENV_CP_MODE = ENV_PREFIX + "CONTEXT_PARALLEL_MODE"    # none|ring|ulysses
ENV_CP_DEGREE = ENV_PREFIX + "CONTEXT_PARALLEL_DEGREE"  # seq-axis size

# Legacy names also honoured so `RANK/WORLD_SIZE`-style launchers keep working
# (ref state.py:215-237 rendezvous env protocol).
LEGACY_RANK_VARS = ("RANK", "PMI_RANK", "OMPI_COMM_WORLD_RANK")
LEGACY_WORLD_VARS = ("WORLD_SIZE", "PMI_SIZE", "OMPI_COMM_WORLD_SIZE")

# --- mesh axis names ---------------------------------------------------------
# One GSPMD mesh replaces the reference's DDP/FSDP/DeepSpeed/Megatron plugin zoo
# (SURVEY.md §7). Canonical axis order: outermost (slowest, DCN-friendly) first.
AXIS_DATA = "data"        # pure data parallel (DDP / ZeRO-0)
AXIS_FSDP = "fsdp"        # parameter/optimizer sharding (FSDP / ZeRO-1/2/3)
AXIS_MODEL = "model"      # tensor parallel (Megatron TP)
AXIS_SEQ = "seq"          # sequence/context parallel (ring attention)
AXIS_EXPERT = "expert"    # MoE expert parallel
AXIS_STAGE = "stage"      # pipeline parallel
MESH_AXES = (AXIS_DATA, AXIS_FSDP, AXIS_STAGE, AXIS_EXPERT, AXIS_SEQ, AXIS_MODEL)
# axis-size sentinel: "one per DCN domain" — resolved by MeshConfig.build
# against the live topology (slice count on TPU pods; process count in
# multi-process CPU worlds; dropped entirely when there is one domain).
# -1 ("fill with remaining devices") stays the ordinary wildcard.
DCN_FILL = -2

# Axes over which a batch is split (data-like axes): gradients are averaged
# over these; per-host data loading shards over them.
BATCH_AXES = (AXIS_DATA, AXIS_FSDP)

SCHEDULER_STEP_KEY = "step"

# TPU generations -> peak bf16 FLOPs/chip (for MFU meters; public specs).
TPU_PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}


def tpu_peak_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of the TPU generation `device_kind` names (as
    `jax.devices()[0].device_kind` reports it). A kind that is not in
    `TPU_PEAK_FLOPS` is an error, never a default: a utilization computed
    against an assumed peak is a made-up number."""
    kind = device_kind.lower()
    for key, flops in TPU_PEAK_FLOPS.items():
        if key in kind:
            return flops
    raise ValueError(
        f"no peak FLOP/s known for device kind {device_kind!r}; add it to "
        f"TPU_PEAK_FLOPS (known: {sorted(TPU_PEAK_FLOPS)}) with its source")
