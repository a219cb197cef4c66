"""Parallelism engines: data (DDP), tensor, sequence (ring attention),
pipeline (GPipe + 1F1B over pp), expert (Switch MoE over ep), and the composed
GSPMD mesh trainer — all built over ONE mesh-addressed pjit front door
(:mod:`.front_door`: spec-driven dp/fsdp/tp/ZeRO-1, whole-step buffer
donation, reshard-free pjit-to-pjit handoff; docs/front_door.md)."""
from . import (data_parallel, front_door, fsdp, moe, pipeline, sequence,
               spmd, tensor)
from .data_parallel import (DataParallel, make_eval_step,
                            make_scan_train_steps, make_stateful_eval_step,
                            make_stateful_train_step, make_train_step,
                            mp_cast_params, prepare_ddp_model, stack_state)
from .front_door import (FROM_INPUTS, Buffers, FrontDoorStep, HandoffMismatch,
                         StepSpecs, handoff_shardings, make_step,
                         verify_handoff)
from .fsdp import (fsdp_param_specs, make_fsdp_train_step,
                   make_zero1_train_step, make_zero2_train_step,
                   opt_state_specs, shard_layouts, shard_model_and_opt)
from .moe import MoELayer, moe_param_specs
from .pipeline import (make_gspmd_pipeline_fn, make_pipeline_train_fn,
                       pipeline_apply, stack_layer_params)
from .sequence import (make_ring_attn_fn, make_ring_flash_attn_fn,
                       ring_attention, ring_flash_attention,
                       stripe_tokens, striped_ring_flash_attention,
                       ulysses_attention, unstripe_tokens)
from .spmd import (make_gspmd_ring_attn_fn,
                   make_gspmd_striped_ring_attn_fn, make_spmd_train_step,
                   shard_batch_spec)
from .tensor import (replicated_specs, shard_params,
                     transformer_lm_param_specs)
