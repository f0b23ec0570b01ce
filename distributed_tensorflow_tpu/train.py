"""CLI training driver — the TPU-native ``distributed.py``.

Launch shape is preserved from the reference (``README.md:7-15``), one process
per TPU-VM host, no CUDA env vars::

    python -m distributed_tensorflow_tpu.train --job_name=worker --task_index=0 \
        --worker_hosts=host0:2223,host1:2224 --sync_replicas=true

A ``--job_name=ps`` process only hosts the coordination service and blocks
(``server.join()`` parity, reference ``distributed.py:55-56``); parameters live
in TPU HBM, not on it.

Reference call-stack parity, stage by stage: flag validation
(``distributed.py:40-47``), cluster/server bring-up (``:49-57``), chief
election (``:58``), model+optimizer (``:65-106``), supervisor/session
(``:108-131``), training loop with validation/logging/final test
(``:133-165``).

``--model`` selects from the BASELINE.json config ladder — ``mnist_mlp``
(default, the reference model), ``lenet5``, ``resnet20``, ``bert_tiny`` —
plus the beyond-parity workloads ``bert_moe`` and ``gpt_mini``.
"""

from __future__ import annotations

import contextlib
import os
import time

import jax

import jax.numpy as jnp

from .config import app, define_training_flags, flags, validate_role_flags
from .cluster.spec import ClusterSpec, is_chief
from .cluster.server import TpuServer
from .models import registry
from .parallel import mesh as mesh_lib
from .parallel import sync as sync_lib
from .training.loop import run_training_loop
from .training.optimizers import schedule_from_flags
from .training.preemption import ShutdownSignal
from .training.supervisor import Supervisor
from .utils import MetricsLogger, SummaryWriter, faults, profiling
from .utils.backend import configure_backend

FLAGS = define_training_flags()
flags.DEFINE_string("mode", "train",
                    "train (default), eval, or generate. eval: restore the "
                    "latest checkpoint from --logdir and report validation + "
                    "test accuracy, no training (sync-layout checkpoints; "
                    "async runs save per-replica stacks). generate: decode "
                    "--gen_tokens tokens from a seed prompt (gpt_mini only)")
flags.DEFINE_integer("gen_tokens", 32, "Tokens to generate in --mode=generate")
flags.DEFINE_string("gen_prompt", "",
                    "Comma-separated token ids to seed --mode=generate "
                    "(default: a stream-sampled prompt)")
flags.DEFINE_string("gen_prompt_text", "",
                    "Text prompt for --mode=generate, encoded with the "
                    "run's saved tokenizer (logdir tokenizer.json; exists "
                    "for corpus-trained runs)")
flags.DEFINE_float("gen_temperature", 0.0,
                   "Sampling temperature in --mode=generate (0 = greedy)")
flags.DEFINE_integer("gen_beams", 1,
                     "Beam width in --mode=generate (1 = greedy/sampled "
                     "decode; >1 runs beam search over the KV-cached path "
                     "— exclusive with --gen_temperature)")
flags.DEFINE_integer("gen_eos_id", -1,
                     "Stop token for --mode=generate (-1 = none): each "
                     "sequence stops at its own terminator, the decode "
                     "loop exits early when all have stopped, and beam "
                     "search freezes finished beams (GNMT length penalty "
                     "at selection)")
flags.DEFINE_float("gen_length_penalty", 1.0,
                   "Beam-search length penalty exponent (used with "
                   "--gen_eos_id; 1.0 = GNMT default, larger favors "
                   "longer continuations)")
flags.DEFINE_string("gen_stop_text", "",
                    "Stop STRING for --mode=generate text output: the "
                    "decoded text is truncated at its first occurrence "
                    "(host-side; needs the run's tokenizer like "
                    "--gen_prompt_text)")
flags.DEFINE_integer("gen_speculative", 0,
                     "Speculative greedy decoding in --mode=generate: "
                     "chunk size for prompt-lookup drafting + one-pass "
                     "verification "
                     "(0 = off; >= 2 = chunk size; the plain greedy "
                     "tokens, fewer device calls on repetitive text; "
                     "exclusive with sampling/beams; full-length cache "
                     "only, so not with --attention_window)")
flags.DEFINE_integer("gen_top_k", 0, "top-k filter in --mode=generate")
flags.DEFINE_float("gen_top_p", 0.0, "nucleus top-p filter in --mode=generate")
flags.DEFINE_string("gen_quantize", "",
                    "--mode=generate weight quantization: '' (off) | int8 "
                    "(per-channel weight-only; weights ride HBM as int8, "
                    "dequant fused into the matmuls — the decode-bandwidth "
                    "lever)")
flags.DEFINE_string("gen_kv_dtype", "",
                    "--mode=generate KV-cache dtype: '' (compute dtype) | "
                    "bfloat16 | float8 (float8_e4m3fn — half of bf16's "
                    "cache bytes, upcast on read; the bandwidth lever for "
                    "long-context decode)")
flags.DEFINE_string("model", "mnist_mlp",
                    "Model/workload: mnist_mlp | lenet5 | resnet20 | "
                    "vit_tiny | bert_tiny | bert_moe | gpt_mini")
flags.DEFINE_string("logdir", "/tmp/dtf_tpu_train",
                    "Checkpoint/recovery directory (stable, unlike the "
                    "reference's tempfile.mkdtemp() — SURVEY §5)")
flags.DEFINE_integer("save_interval_steps", 1000, "Checkpoint every N global steps")
flags.DEFINE_integer("max_checkpoints_to_keep", 3,
                     "Checkpoint retention: keep the last K checkpoints so "
                     "long runs don't fill the disk — plus, always, the "
                     "newest one that passes integrity verification "
                     "(docs/fault_tolerance.md). 0 keeps everything")
flags.DEFINE_integer("log_every", 1, "Print metrics every N local steps")
flags.DEFINE_integer("validation_every", 10000,
                     "Evaluate the validation split every N local steps "
                     "(reference hardcodes 10000, distributed.py:140); 0 "
                     "disables periodic validation")
flags.DEFINE_string("async_mode", "local_sgd",
                    "TPU-native async flavor when --sync_replicas=false with >1 "
                    "replica: 'local_sgd' (periodic parameter averaging)")
flags.DEFINE_integer("async_sync_period", 16,
                     "Local steps between parameter averages in async mode")
flags.DEFINE_boolean("async_overlap_exchange", False,
                     "Run the async parameter exchange in a BACKGROUND "
                     "thread: publish/fetch/average overlap with training "
                     "and the consensus is applied one period late as a "
                     "delta against its snapshot (local steps taken "
                     "meanwhile are preserved). Hides the GB-scale "
                     "exchange stall behind compute — see "
                     "cluster/param_sync.OverlappedAverager")
flags.DEFINE_string("async_compress", "off",
                    "Compressed sharded parameter exchange for async mode: "
                    "'int8' (per-block-scaled int8 deltas with error "
                    "feedback), 'bf16' (bf16 deltas), or 'off' (full-state "
                    "exchange, the pre-compression wire format). Deltas "
                    "against the agreed consensus travel reduce-scattered "
                    "across the active membership — O(2P/N) quantized bytes "
                    "instead of O(N*P) full precision "
                    "(docs/param_exchange.md)")
flags.DEFINE_integer("async_anchor_every", 8,
                     "Full-state anchor cadence (consensus rounds) of the "
                     "compressed exchange: rejoining/elastic workers "
                     "bootstrap from the anchor, laggards resync to it")
flags.DEFINE_integer("async_quant_block", 1024,
                     "Elements per quantization scale block in the "
                     "compressed exchange's int8 format")
flags.DEFINE_integer("slice_size", 0,
                     "Hierarchical compressed exchange: workers per slice. "
                     "Within a slice deltas reduce RAW (ICI/shared-memory "
                     "class, never quantized); one exporter per slice runs "
                     "the quantized shard exchange against the other "
                     "slices' exporters, cutting per-host inter-host bytes "
                     "from O(2P/N*N) to O(2P/S). 0 = auto from the mesh "
                     "topology (--dcn_data_parallel slices when it divides "
                     "the worker count, else flat); 1 = flat "
                     "(docs/param_exchange.md, 'Hierarchical exchange')")
flags.DEFINE_string("coord_standbys", "",
                    "Coordinator / KV-shard HA (docs/fault_tolerance.md): "
                    "warm-standby endpoints (launched via "
                    "tools/coord_shard.py --standby_of).  Either a "
                    "comma-separated host:port list — standbys of the "
                    "CONTROL shard — or a per-instance map "
                    "'0:host:port[,host:port];1:host:port' wiring an "
                    "ordered standby list for every coordinator instance "
                    "of a sharded plane.  Workers walk the owning "
                    "instance's list on a dead or demoted primary — and "
                    "fence stale generations via the reply trailer — so a "
                    "SIGKILLed coordinator or KV-shard primary is a stall "
                    "bounded by the leadership lease, not an outage")
flags.DEFINE_integer("coord_instances", 1,
                     "Sharded coordination plane: number of coordinator "
                     "instances. Instance i listens on the coordinator "
                     "port + i; KV/blob traffic spreads across instances "
                     "by stable key hash while membership/barrier/lease "
                     "traffic stays pinned to instance 0 (the control "
                     "shard). Workers speak through a CoordinationRouter; "
                     "1 = the classic single coordinator")
flags.DEFINE_integer("bert_seq_len", 128,
                     "Sequence length for transformer models "
                     "(bert_tiny, bert_moe, gpt_mini)")
flags.DEFINE_float("bert_dropout", 0.0,
                   "Dropout rate for transformer models (0 keeps training "
                   "deterministic, the historical default here; BERT's own "
                   "recipe uses 0.1). Sync mode only")
flags.DEFINE_string("bert_dtype", "bfloat16",
                    "Activation dtype for transformer models (bfloat16 is "
                    "MXU-native; params stay fp32): bfloat16 | float32")
flags.DEFINE_boolean("remat", False,
                     "Rematerialize transformer layers in the backward pass "
                     "(jax.checkpoint): recompute activations instead of "
                     "holding them in HBM — for long sequences/deep stacks")
flags.DEFINE_integer("tensor_parallel", 1,
                     "Size of the 'model' mesh axis (tensor parallelism); the "
                     "data axis is inferred from the remaining devices")
flags.DEFINE_integer("sequence_parallel", 1,
                     "Size of the 'seq' mesh axis (sequence/context "
                     "parallelism; pairs with --attention_backend=ring "
                     "or ulysses)")
flags.DEFINE_integer("pipeline_parallel", 1,
                     "Size of the 'pipe' mesh axis (GPipe pipeline "
                     "parallelism; currently --model=gpt_mini only)")
flags.DEFINE_integer("pipeline_virtual_stages", 2,
                     "Model chunks per pipe rank with "
                     "--pipeline_schedule=interleaved (Megatron virtual "
                     "pipeline stages: round-robin chunk assignment shrinks "
                     "the fill/drain bubble ~v-fold; needs "
                     "--pipeline_microbatches divisible by "
                     "--pipeline_parallel and num_layers divisible by "
                     "pipe*v)")
flags.DEFINE_integer("pipeline_microbatches", 4,
                     "Microbatches per pipeline step (global batch must "
                     "divide into data shards x microbatches)")
flags.DEFINE_string("pipeline_schedule", "gpipe",
                    "Pipeline schedule: gpipe (default; AD through the "
                    "scan) | 1f1b (one-forward-one-backward: hand-rolled "
                    "backward, activation stash bounded by pipeline depth "
                    "instead of microbatch count) | interleaved (1F1B over "
                    "--pipeline_virtual_stages round-robin model chunks per "
                    "rank — Megatron virtual pipeline stages, ~v-fold "
                    "smaller fill/drain bubble)")
flags.DEFINE_boolean("sharded_feed", True,
                     "Multi-controller runs: each process loads only its "
                     "slice of the global batch (disjoint per-process data "
                     "streams assembled with "
                     "jax.make_array_from_process_local_data) instead of "
                     "every host materializing the full batch. Auto-falls "
                     "back (with a log line) for seq-sharded layouts, "
                     "indivisible batch sizes, or splits without shard()")
flags.DEFINE_boolean("fsdp", False,
                     "ZeRO-3/FSDP: shard parameters, optimizer state, and "
                     "EMA over the 'data' mesh axis in HBM (GSPMD inserts "
                     "the all-gather/reduce-scatter); composes with "
                     "--tensor_parallel. Cuts per-chip param+opt memory by "
                     "~the data-axis size. Sync mode only")
flags.DEFINE_integer("fsdp_min_size", 65536,
                     "FSDP: parameter leaves smaller than this many elements "
                     "stay replicated (sharding tiny tensors costs an "
                     "all-gather for no memory win)")
flags.DEFINE_integer("dcn_data_parallel", 1,
                     "Multi-slice pods: outer factor of the 'data' axis that "
                     "crosses slice boundaries over DCN (devices ordered "
                     "slice-major; all other axes stay on intra-slice ICI). "
                     "1 = single slice")
flags.DEFINE_integer("expert_parallel", 1,
                     "Size of the 'expert' mesh axis (expert parallelism; "
                     "pairs with --model=bert_moe)")
flags.DEFINE_integer("num_experts", 4,
                     "Number of MoE experts for --model=bert_moe")
flags.DEFINE_string("attention_backend", "xla",
                    "Attention backend for transformer models: xla | pallas | "
                    "ring | ulysses (ring = ppermute K/V hops, ulysses = "
                    "head/sequence all-to-all, heads divisible by "
                    "--sequence_parallel; both need --sequence_parallel > 1)")
flags.DEFINE_string("gpt_positions", "learned",
                    "Position encoding for gpt_mini: learned (absolute "
                    "embedding table) | rope (rotary, relative)")
flags.DEFINE_string("gpt_activation", "gelu",
                    "gpt_mini MLP activation: gelu (GPT-2 style) | swiglu "
                    "(gated SiLU, Llama-style — adds a gate matrix)")
flags.DEFINE_string("gpt_norm", "layernorm",
                    "gpt_mini normalization: layernorm | rmsnorm "
                    "(no mean-centering/bias, Llama-style)")
flags.DEFINE_integer("attention_window", 0,
                     "Sliding-window attention for gpt_mini (0 = full "
                     "causal): each token attends its last N predecessors "
                     "only; the pallas backend skips whole blocks outside "
                     "the band (O(S*N) compute). Training, prefill, and the "
                     "decode cache all apply the same window")
flags.DEFINE_string("gpt_tokenizer", "byte",
                    "Text tokenizer for the gpt_mini *.txt corpus: byte "
                    "(ids = raw bytes, vocab 256) | bpe (byte-level BPE "
                    "trained on the corpus train split via the C++ core in "
                    "src/tokenizer/bpe.cc; model vocab = --gpt_bpe_vocab)")
flags.DEFINE_integer("gpt_bpe_vocab", 512,
                     "Model vocab size with --gpt_tokenizer=bpe (includes "
                     "the 256 base bytes; the merge table is trained up to "
                     "this many tokens)")
flags.DEFINE_integer("gpt_stream_corpus_mb", 256,
                     "Corpus size (MB of *.txt under --data_dir) above "
                     "which the LM corpus streams in chunks instead of "
                     "loading into RAM: per-process disjoint chunk sets, "
                     "deterministic cursor resume (saved at checkpoints); "
                     "BPE then trains on a bounded train-region sample")
flags.DEFINE_integer("gpt_kv_heads", 0,
                     "Grouped-query attention for gpt_mini: number of K/V "
                     "heads (must divide the head count; 1 = MQA). Query "
                     "heads share K/V in groups, shrinking the decode KV "
                     "cache and its HBM reads by heads/kv_heads. 0 "
                     "(default) = plain multi-head attention")
flags.DEFINE_boolean("gpt_matmul_int8", False,
                     "Quantized TRAINING for gpt_mini: route the MLP "
                     "matmuls through the MXU's int8 path — int8 forward "
                     "+ input-gradient matmuls, full-precision weight "
                     "gradients (SwitchBack; ops/quant_train.py). Same "
                     "checkpoint tree as bf16; convergence tracks bf16 "
                     "within ~2%. On the chip the gelu MLP runs through "
                     "fused pallas kernels (epilogue/NT-backward fusion). "
                     "Its speed against bf16 is not measured on this tree "
                     "(ROADMAP S10)")
flags.DEFINE_boolean("gpt_attn_int8", False,
                     "Also route gpt_mini's ATTENTION projections "
                     "(qkv/out) through the int8 path. Its speed against "
                     "the MLP-only int8 step is not measured on this tree "
                     "(ROADMAP S10)")
flags.DEFINE_boolean("gen_speculative_device", True,
                     "Run --gen_speculative ENTIRELY on device (draft + "
                     "verify + accept in one lax.while_loop): one dispatch "
                     "for the whole generation instead of a host round "
                     "trip per round, with a cached compiled program, "
                     "incremental n-gram index drafting, tree "
                     "verification, and adaptive K (docs/speculative.md; "
                     "its speed against plain decoding is not measured on "
                     "this tree, ROADMAP S10). The DEFAULT "
                     "speculative path; set false for the host loop's "
                     "per-round stats and explicit fallback telemetry")
flags.DEFINE_float("label_smoothing", 0.0,
                   "Mix one-hot training targets with the uniform "
                   "distribution: (1-a)*onehot + a/K (all models; 0 = off)")
flags.DEFINE_boolean("data_augmentation", False,
                     "Train-time data augmentation where the pipeline "
                     "defines one (resnet20/CIFAR: reflect-pad-4 random "
                     "crop + horizontal flip)")
flags.DEFINE_boolean("log_grad_norm", False,
                     "Add the global gradient L2 norm to each step's metrics "
                     "(JSONL records and TensorBoard summaries; sync "
                     "plain/scanned/accumulating steps)")
flags.DEFINE_boolean("fused_layer_norm", False,
                     "Route transformer LayerNorms through the pallas "
                     "kernel (ops/pallas/layer_norm.py); same math and "
                     "parameter tree as nn.LayerNorm. Its speed against "
                     "XLA's own LN fusion is not measured on this tree "
                     "(ROADMAP S10)")
flags.DEFINE_string("optimizer", "",
                    "Override the model's optimizer: sgd | momentum | "
                    "nesterov | adam | adamw | lamb | adagrad | rmsprop | "
                    "adafactor (factored second moments — sublinear "
                    "optimizer memory). Empty (default) keeps the model's "
                    "own choice (SGD for the reference workloads, Adam for "
                    "transformers)")
flags.DEFINE_string("trainable_params", "",
                    "Selective fine-tuning: regex over parameter paths "
                    "(e.g. 'head' or 'layer3|head'); only matching params "
                    "train, the rest are frozen with zero updates and no "
                    "optimizer slots. Empty (default) trains everything. "
                    "Checkpoints carry the masked optimizer layout — resume "
                    "with the same pattern")
flags.DEFINE_float("momentum", 0.9, "Momentum for momentum/nesterov/rmsprop")
flags.DEFINE_float("weight_decay", 0.0,
                   "Weight decay with --optimizer: true decoupled decay for "
                   "adamw/lamb; classic L2 regularization for the others")
flags.DEFINE_string("lr_schedule", "constant",
                    "Learning-rate schedule with --optimizer: constant | "
                    "cosine | linear | rsqrt")
flags.DEFINE_integer("warmup_steps", 0, "Linear lr warmup steps")
flags.DEFINE_integer("decay_steps", 0,
                     "Schedule horizon; 0 means --train_steps")
flags.DEFINE_float("end_lr_factor", 0.0,
                   "Final lr as a fraction of the peak (cosine/linear)")
flags.DEFINE_float("grad_clip_norm", 0.0,
                   "Clip gradients to this global norm before the update "
                   "(0 disables; requires --optimizer, like the other "
                   "tuning knobs here)")
flags.DEFINE_float("heartbeat_timeout", 10.0,
                   "Seconds without a heartbeat before the coordination "
                   "service marks a worker dead (drives the R<N replica mask)")
flags.DEFINE_string("elastic_mode", "auto",
                    "Elastic membership (docs/fault_tolerance.md): react to "
                    "coordination-service membership-epoch changes instead "
                    "of stalling behind dead workers. auto (default): "
                    "'in_place' on the single-controller masked (R<N) sync "
                    "path, 'reshard' on multi-controller sync runs, off "
                    "otherwise. in_place: an epoch change flips the "
                    "per-replica mask (survivors keep stepping at R<N); an "
                    "evicted worker pauses, re-registers, restores the "
                    "chief's latest published checkpoint, and resumes. "
                    "reshard: the chief reacts to a shrink by publishing a "
                    "stop step; all processes checkpoint there and exit "
                    "with the new cluster spec published for relaunch. "
                    "off: PR-2 behavior (lease-expiry health masking only)")
flags.DEFINE_integer("elastic_reshard_margin", 20,
                     "reshard mode: steps between the chief announcing a "
                     "reshard and the collective stop-and-checkpoint; must "
                     "exceed membership-poll-interval x step-rate so every "
                     "process learns the stop step before reaching it")
flags.DEFINE_integer("straggler_lag", 0,
                     "R<N masked sync: a slow-but-alive worker whose "
                     "heartbeat-reported step falls more than this many "
                     "steps behind the front-runner is dropped from the "
                     "live set until it catches back up (the reference "
                     "SyncReplicasOptimizer's drop-the-slow semantics, "
                     "distributed.py:97-100). 0 (default) drops only on "
                     "heartbeat death")
flags.DEFINE_string("inject_step_delay", "",
                    "Fault injection: comma-separated 'SECS:N' (sleep SECS "
                    "after each of the first N local steps) or "
                    "'SECS:START:END' (delay local steps in [START, END)) "
                    "windows; sleeps of overlapping windows add. Exercises "
                    "straggler tolerance (--straggler_lag) without hacking "
                    "the clock; empty disables")
flags.DEFINE_integer("steps_per_call", 1,
                     "Optimizer steps per device dispatch (lax.scan chunk). "
                     ">1 amortizes host dispatch across a chunk; logging/"
                     "validation/checkpoints move to chunk boundaries. "
                     "log_every and validation intervals must be multiples. "
                     "Incompatible with R<N masking; in async mode it must "
                     "equal --async_sync_period (one dispatch per period)")
flags.DEFINE_integer("grad_accum_steps", 1,
                     "Accumulate gradients over N microbatches per optimizer "
                     "step (one update on the mean gradient — large global "
                     "batch with one microbatch's activation memory). Sync "
                     "mode only; exclusive with --steps_per_call")
flags.DEFINE_float("ema_decay", 0.0,
                   "Maintain an exponential moving average of the weights "
                   "with this decay (e.g. 0.999); evaluation and the final "
                   "test then use the EMA copy. Sync mode (plain/scanned/"
                   "accumulating steps) only; 0 disables")
flags.DEFINE_boolean("log_sharding", False,
                     "Print each parameter's placement at startup — the "
                     "log_device_placement equivalent (reference "
                     "distributed.py:115), per mesh axis instead of device")
flags.DEFINE_boolean("graceful_shutdown", True,
                     "On SIGTERM (pod preemption) or SIGINT (Ctrl-C): "
                     "finish the in-flight step, write a checkpoint, exit "
                     "cleanly")
flags.DEFINE_integer("seed", 0,
                     "Model-initialization seed (all workers must agree: "
                     "SPMD requires identical initial state everywhere). "
                     "Synthetic data streams are deterministic regardless")
flags.DEFINE_integer("prefetch", 2,
                     "Host->device input prefetch depth (background thread; "
                     "0 disables and feeds synchronously)")
flags.DEFINE_string("feed_dtype", "float32",
                    "Training-feed image dtype: float32 (default) | uint8 "
                    "(ship raw bytes host->device — 4x fewer feed bytes — "
                    "and normalize by 255 on device; image models only)")
flags.DEFINE_string("metrics_file", None,
                    "Append structured JSONL metric records here (SURVEY §5 "
                    "observability; default: stdout prints only, like the "
                    "reference)")
flags.DEFINE_boolean("telemetry", True,
                     "With --metrics_file: full run telemetry in the same "
                     "JSONL stream — per-step data-wait/compute breakdown "
                     "(the step dispatch is synced each step for honest "
                     "timing), live MFU, HBM high-watermarks, eval/"
                     "checkpoint pause records, cluster health snapshots, "
                     "and a final run_summary with whole-run histogram "
                     "quantiles (docs/observability.md; render with "
                     "tools/summarize_run.py). false: bare metric records "
                     "only, no per-step device sync")
flags.DEFINE_float("peak_tflops", 0.0,
                   "Per-chip peak TFLOP/s for the telemetry MFU figure "
                   "(0 = auto from the device kind table in "
                   "tools/cost_model.py; set explicitly on unknown chips "
                   "or CPU smoke runs to get a non-null mfu)")
flags.DEFINE_float("health_report_every", 10.0,
                   "Seconds between cluster-health telemetry snapshots "
                   "(peer heartbeat ages, live set, straggler gap) when a "
                   "coordination service is attached; 0 disables")
flags.DEFINE_string("summary_dir", None,
                    "Write TensorBoard scalar summaries (tfevents files) "
                    "here, chief only — the Supervisor summary path the "
                    "reference wired but never used (SURVEY §5)")
flags.DEFINE_boolean("summary_histograms", False,
                     "Also write per-parameter weight histograms at the "
                     "validation cadence (requires --summary_dir)")
flags.DEFINE_string("profile_dir", None,
                    "Capture a JAX/XLA profile of the training loop into this "
                    "directory (TensorBoard-loadable)")
flags.DEFINE_string("platform", None,
                    "Force a JAX platform ('cpu', 'tpu') for this process; "
                    "same effect as JAX_PLATFORMS in the environment "
                    "(default: JAX picks — the TPU where one is attached)")
flags.DEFINE_string("profile", "",
                    "Run under a tuned run profile "
                    "(tools/autotune.py output, docs/autotune.md): the "
                    "profile's declarative ParallelConfig overrides the "
                    "parallelism flags (tensor/sequence/pipeline/expert "
                    "parallel, grad accumulation, int8 arm, fsdp) and its "
                    "workload section overrides --model/--batch_size/"
                    "--bert_seq_len, so the tuned layout reproduces end "
                    "to end. Explicit flags that the profile also sets "
                    "are overridden (the profile is the layout of "
                    "record); everything else keeps its flag value")


#: run-profile parallel field -> training flag it overrides (the
#: ParallelConfig <-> flag mapping, inverse of ParallelConfig.from_flags).
#: ``microbatch`` is handled separately: on a pipeline layout it means
#: pipeline microbatches, otherwise gradient accumulation.
_PROFILE_PARALLEL_FLAGS = (
    ("model", "tensor_parallel"),
    ("seq", "sequence_parallel"),
    ("pipe", "pipeline_parallel"),
    ("expert", "expert_parallel"),
    ("dcn_data", "dcn_data_parallel"),
    ("fsdp", "fsdp"),
    ("fsdp_min_size", "fsdp_min_size"),
)
_PROFILE_WORKLOAD_FLAGS = (
    ("model", "model"),
    ("batch_size", "batch_size"),
    ("seq_len", "bert_seq_len"),
    ("hidden_units", "hidden_units"),
    ("bert_dtype", "bert_dtype"),
    ("pipeline_schedule", "pipeline_schedule"),
    ("remat", "remat"),
    ("attention_window", "attention_window"),
    ("kv_heads", "gpt_kv_heads"),
)


def apply_run_profile(FLAGS) -> tuple[dict, "object"]:
    """Load ``--profile`` and fold it into the flag set; returns the
    ({flag: value} overrides applied, the profile's ParallelConfig or
    None).

    The profile is authoritative for what it covers — a tuned layout must
    reproduce even when the command line still carries the old flags —
    and silent about everything else.  The returned config (data axis
    pinned to the tuned size, not -1) is what main() builds the mesh
    from, so a dp1 winner reproduces its 1-device submesh even on a
    bigger host.
    """
    from .parallel import mesh as mesh_lib
    payload = mesh_lib.load_run_profile(FLAGS.profile)
    applied: dict = {}
    pcfg = None
    parallel = payload.get("parallel")
    if parallel:
        pcfg = mesh_lib.ParallelConfig.from_dict(parallel)
        for field, flag in _PROFILE_PARALLEL_FLAGS:
            value = getattr(pcfg, field)
            if getattr(FLAGS, flag) != value:
                setattr(FLAGS, flag, value)
                applied[flag] = value
        # microbatch means pipeline microbatches on a pipe layout (where
        # grad accumulation is rejected as redundant) and gradient
        # accumulation everywhere else; the unused knob is reset so a
        # stale command-line value can't fail the pipeline cross-checks.
        micro_flag = ("pipeline_microbatches" if pcfg.pipe > 1
                      else "grad_accum_steps")
        if getattr(FLAGS, micro_flag) != pcfg.microbatch:
            setattr(FLAGS, micro_flag, pcfg.microbatch)
            applied[micro_flag] = pcfg.microbatch
        if pcfg.pipe > 1 and FLAGS.grad_accum_steps != 1:
            FLAGS.grad_accum_steps = 1
            applied["grad_accum_steps"] = 1
        # The quantize arm is authoritative BOTH ways: an 'off' winner
        # must clear a stale --gpt_matmul_int8=true.
        want_int8 = pcfg.quantize == "int8"
        if FLAGS.gpt_matmul_int8 != want_int8:
            FLAGS.gpt_matmul_int8 = want_int8
            applied["gpt_matmul_int8"] = want_int8
        # Likewise the attention backend of record: 'auto' resolves
        # against the seq axis (ring when sharded, xla otherwise — what
        # the winning trial actually ran), so a stale explicit
        # --attention_backend=ring can't survive a dp-only profile.
        backend = pcfg.resolved_attention()
        if FLAGS.attention_backend != backend:
            FLAGS.attention_backend = backend
            applied["attention_backend"] = backend
    for key, flag in _PROFILE_WORKLOAD_FLAGS:
        value = payload.get("workload", {}).get(key)
        if value is not None and getattr(FLAGS, flag) != value:
            setattr(FLAGS, flag, value)
            applied[flag] = value
    return applied, pcfg


def run_generate():
    """Inference entry point: restore the newest checkpoint and decode.

    Restores *raw arrays* (no state template), so it works with any training
    configuration: optimizer slots are ignored, EMA weights are preferred
    when present, and a ``--pipeline_parallel`` run's stage-stacked tree is
    merged back into the plain layout.  The decode path hand-rolls its
    attention against the KV cache, so no attention backend or mesh setup is
    needed.
    """
    if FLAGS.model != "gpt_mini":
        raise ValueError(
            f"--mode=generate needs an autoregressive model "
            f"(--model=gpt_mini), got --model={FLAGS.model}")
    import dataclasses as _dc

    import numpy as np
    import orbax.checkpoint as ocp

    from .models import gpt as gpt_lib

    # Mirror the training run's checkpoint namespace (registry.py bundles).
    if FLAGS.pipeline_parallel > 1:
        name = registry.pipeline_bundle_name(FLAGS.pipeline_parallel,
                                             FLAGS.pipeline_schedule,
                                             FLAGS.pipeline_virtual_stages)
    else:
        name = "gpt_mini"
    # One cfg construction shared with the builders: mini() + the same flag
    # overrides build_gpt_mini applies.  The attention backend is
    # DELIBERATELY left at the default: prefill dispatches on it, and the
    # ring backend (training-time seq sharding) has no mesh at decode.
    cfg = _dc.replace(gpt_lib.mini(), dtype=FLAGS.bert_dtype,
                      pos_encoding=FLAGS.gpt_positions,
                      kv_heads=FLAGS.gpt_kv_heads,
                      attention_window=FLAGS.attention_window,
                      activation=FLAGS.gpt_activation, norm=FLAGS.gpt_norm)

    ckpt_dir = os.path.join(FLAGS.logdir, name, "checkpoints")
    restored_step, params = 1, None
    if os.path.isdir(ckpt_dir):
        mgr = ocp.CheckpointManager(ckpt_dir)
        step = mgr.latest_step()
        if step is not None:
            restored = mgr.restore(step, args=ocp.args.StandardRestore())
            restored_step = int(np.asarray(restored["global_step"]))
            tree = restored.get("ema_params") or restored["params"]
            if "stages" in tree:  # pipelined checkpoint -> plain layout
                tree = gpt_lib.merge_pipeline_params(
                    tree, cfg.num_layers,
                    n_virtual=(FLAGS.pipeline_virtual_stages
                               if FLAGS.pipeline_schedule == "interleaved"
                               else 1))
            params = tree
            layer0 = tree.get("layer0", {})
            if "word_emb" in tree:
                # BPE-trained checkpoints carry a wider embedding table;
                # infer the vocab so the caller need not re-pass the flags.
                cfg = _dc.replace(
                    cfg,
                    vocab_size=int(tree["word_emb"]["embedding"].shape[0]))
            if layer0:
                # Architecture knobs the checkpoint itself reveals (shared
                # inference with export): the tree is ground truth — a
                # mismatched cfg could not apply these params — so explicit
                # flags that disagree are overridden with a warning.
                arch = gpt_lib.infer_arch_from_layer0(layer0)
                kv_inferred = arch.pop("kv_heads", 0)
                if kv_inferred and not FLAGS.gpt_kv_heads:
                    cfg = _dc.replace(cfg, kv_heads=kv_inferred)
                for flag, knob in (("gpt_activation", "activation"),
                                   ("gpt_norm", "norm")):
                    passed = getattr(FLAGS, flag)
                    if passed != arch[knob] and passed != getattr(
                            gpt_lib.mini(), knob):
                        print(f"WARNING: --{flag}={passed} does not match "
                              f"the checkpoint ({arch[knob]}); using the "
                              "checkpoint's architecture")
                cfg = _dc.replace(cfg, **arch)
        mgr.close()
    model = gpt_lib.GptLM(cfg)
    if params is None:
        print(f"WARNING: no checkpoint found under {ckpt_dir}; "
              "generating from random init")
        dummy = jnp.zeros((1, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(FLAGS.seed), dummy)["params"]

    # Corpus-trained runs persist their tokenizer next to the checkpoints;
    # when present, --gen_prompt_text encodes through it and the output is
    # additionally decoded to text.
    tok = None
    tok_path = os.path.join(FLAGS.logdir, name, "tokenizer.json")
    if os.path.exists(tok_path):
        from .data.tokenizer import BpeTokenizer
        tok = BpeTokenizer.load(tok_path)
    if FLAGS.gen_prompt_text:
        if tok is None:
            raise ValueError(
                f"--gen_prompt_text needs the run's tokenizer at {tok_path} "
                "(saved by corpus-trained runs); use --gen_prompt ids instead")
        ids = tok.encode(FLAGS.gen_prompt_text.encode("utf-8")).tolist()
        if not ids:
            raise ValueError("--gen_prompt_text encoded to zero tokens")
        bad = [t for t in ids if not 0 <= t < cfg.vocab_size]
        if bad:
            # e.g. a bpe tokenizer.json left in the logdir next to a
            # byte-vocab checkpoint — fail loudly instead of letting the
            # embedding gather clamp out-of-range ids into garbage.
            raise ValueError(
                f"--gen_prompt_text encoded to ids {bad} outside the "
                f"model's vocab [0, {cfg.vocab_size}); the saved tokenizer "
                "does not match this checkpoint")
        prompt = jnp.asarray([ids], jnp.int32)
    elif FLAGS.gen_prompt:
        ids = [int(t) for t in FLAGS.gen_prompt.split(",")]
        bad = [t for t in ids if not 0 <= t < cfg.vocab_size]
        if bad:
            raise ValueError(f"--gen_prompt ids {bad} outside vocab "
                             f"[0, {cfg.vocab_size})")
        prompt = jnp.asarray([ids], jnp.int32)
    else:
        seq = min(FLAGS.bert_seq_len, cfg.max_position - FLAGS.gen_tokens)
        prompt = jnp.asarray(gpt_lib.synthetic_lm_batch(
            FLAGS.seed, 1, max(seq, 2), cfg)["tokens"][:, :max(seq // 2, 1)])
    eos_id = None if FLAGS.gen_eos_id < 0 else FLAGS.gen_eos_id
    if eos_id is not None and eos_id >= cfg.vocab_size:
        raise ValueError(f"--gen_eos_id {eos_id} outside vocab "
                         f"[0, {cfg.vocab_size})")
    if FLAGS.gen_stop_text and tok is None:
        raise ValueError(
            f"--gen_stop_text needs the run's tokenizer at {tok_path} "
            "(saved by corpus-trained runs) to decode the output")
    if FLAGS.gen_speculative and FLAGS.gen_beams > 1:
        raise ValueError("--gen_speculative is exclusive with --gen_beams")
    if FLAGS.gen_speculative == 1 or FLAGS.gen_speculative < 0:
        raise ValueError(f"--gen_speculative must be 0 (off) or >= 2, got "
                         f"{FLAGS.gen_speculative}")
    # --gen_speculative_device (default true) selects WHICH speculative
    # variant runs; without --gen_speculative=K speculation is simply off
    # and the flag is inert — no cross-flag validation needed.
    if FLAGS.gen_beams > 1:
        if FLAGS.gen_temperature > 0 or FLAGS.gen_top_k or FLAGS.gen_top_p:
            raise ValueError(
                "--gen_beams > 1 is exact-search decoding; it is exclusive "
                "with the sampling flags (--gen_temperature/--gen_top_k/"
                "--gen_top_p)")
        out, logprob = gpt_lib.beam_search_cached(
            model, params, prompt, FLAGS.gen_tokens,
            beam_size=FLAGS.gen_beams, quantize=FLAGS.gen_quantize,
            kv_dtype=FLAGS.gen_kv_dtype, eos_id=eos_id,
            length_penalty=FLAGS.gen_length_penalty)
        print(f"Beam search (width {FLAGS.gen_beams}) best logprob: "
              f"{float(logprob[0]):.4f}")
    elif FLAGS.gen_speculative:
        if FLAGS.gen_temperature > 0 or FLAGS.gen_top_k or FLAGS.gen_top_p:
            raise ValueError(
                "--gen_speculative is greedy-only (verification compares "
                "against argmax); it is exclusive with the sampling flags")
        if FLAGS.gen_speculative_device:
            out, spec_stats = gpt_lib.generate_cached_speculative_device(
                model, params, prompt, FLAGS.gen_tokens,
                spec_k=FLAGS.gen_speculative, eos_id=eos_id,
                quantize=FLAGS.gen_quantize, kv_dtype=FLAGS.gen_kv_dtype)
        else:
            out, spec_stats = gpt_lib.generate_cached_speculative(
                model, params, prompt, FLAGS.gen_tokens,
                spec_k=FLAGS.gen_speculative, eos_id=eos_id,
                quantize=FLAGS.gen_quantize, kv_dtype=FLAGS.gen_kv_dtype)
        fb = spec_stats.get("fallback_at_round")
        small = spec_stats.get("rounds_small", 0)
        print(f"Speculative decode: {spec_stats['tokens_generated']} tokens "
              f"in {spec_stats['rounds']} rounds "
              f"({spec_stats['mean_accepted_per_round']} tokens/round)"
              + (f"; low acceptance — fell back to plain cached decode "
                 f"after round {fb}" if fb is not None else "")
              + (f"; adaptive K ran {small} small round(s)"
                 if small else ""))
    else:
        rng = (jax.random.PRNGKey(FLAGS.seed)
               if FLAGS.gen_temperature > 0 else None)
        out = gpt_lib.generate_cached(
            model, params, prompt, FLAGS.gen_tokens,
            temperature=FLAGS.gen_temperature, top_k=FLAGS.gen_top_k,
            top_p=FLAGS.gen_top_p, rng=rng, quantize=FLAGS.gen_quantize,
            kv_dtype=FLAGS.gen_kv_dtype, eos_id=eos_id)
    toks = np.asarray(out)[0]
    split = prompt.shape[1]
    gen = toks[split:]
    if eos_id is not None:
        # Report up to and including the first terminator; the tail past it
        # is eos padding by construction.
        hits = np.flatnonzero(gen == eos_id)
        if hits.size:
            gen = gen[:hits[0] + 1]
            print(f"Stopped at eos id {eos_id} after {hits[0] + 1} tokens")
    print(f"Restored global step: {restored_step}")
    print(f"Prompt tokens:    {' '.join(map(str, toks[:split]))}")
    print(f"Generated tokens: {' '.join(map(str, gen))}")
    if tok is not None:
        drop = 1 if (eos_id is not None and gen.size and
                     gen[-1] == eos_id) else 0
        text = tok.decode(gen[:gen.size - drop]).decode("utf-8",
                                                        errors="replace")
        if FLAGS.gen_stop_text and FLAGS.gen_stop_text in text:
            text = text.split(FLAGS.gen_stop_text, 1)[0]
            print(f"Stopped at stop text {FLAGS.gen_stop_text!r}")
        print(f"Generated text:   {text!r}")
    return toks


def main(unused_argv):
    configure_backend(FLAGS.platform)

    # Chaos harness: arm any DTF_CHAOS-specified faults before bring-up so
    # subprocess fault-recovery tests can inject without code changes
    # (no-op when the env var is unset — the common case).
    faults.install_from_env()

    # Tuned run profile (docs/autotune.md): fold the winning layout into
    # the flag set BEFORE any validation so every downstream consumer
    # (flag cross-checks, model builders, the mesh) sees the tuned values.
    profile_pcfg = None
    if FLAGS.profile:
        applied, profile_pcfg = apply_run_profile(FLAGS)
        print(f"Worker {FLAGS.task_index}: applying run profile "
              f"{FLAGS.profile}"
              + (f" (layout {profile_pcfg.describe()})"
                 if profile_pcfg is not None else "")
              + (f": overrides {applied}" if applied else ": no overrides"))

    if FLAGS.mode == "generate":
        return run_generate()
    if FLAGS.mode not in ("train", "eval"):
        raise ValueError(
            f"--mode must be train, eval or generate, got {FLAGS.mode}")

    validate_role_flags(FLAGS)
    if FLAGS.feed_dtype not in ("float32", "uint8"):
        raise ValueError(
            f"--feed_dtype must be float32 or uint8, got {FLAGS.feed_dtype}")
    if FLAGS.ema_decay != 0 and not (0 < FLAGS.ema_decay < 1):
        raise ValueError(f"--ema_decay must be in (0, 1), got {FLAGS.ema_decay}")
    if not 0 <= FLAGS.label_smoothing < 1:
        raise ValueError(f"--label_smoothing must be in [0, 1), got "
                         f"{FLAGS.label_smoothing}")
    if FLAGS.attention_window < 0:
        raise ValueError(f"--attention_window must be >= 0, got "
                         f"{FLAGS.attention_window}")
    if FLAGS.gpt_tokenizer not in ("byte", "bpe"):
        raise ValueError(f"--gpt_tokenizer must be byte or bpe, got "
                         f"{FLAGS.gpt_tokenizer!r}")
    if FLAGS.gpt_tokenizer == "bpe":
        from .models.registry import _validate_bpe_vocab
        try:
            _validate_bpe_vocab(FLAGS.gpt_bpe_vocab)
        except ValueError as e:
            raise ValueError(f"--gpt_bpe_vocab: {e}") from None
    if FLAGS.pipeline_parallel > 1:
        if FLAGS.model != "gpt_mini":
            raise ValueError(
                f"--pipeline_parallel needs a homogeneous-block model "
                f"(--model=gpt_mini), got --model={FLAGS.model}")
        if FLAGS.pipeline_schedule == "interleaved":
            if FLAGS.pipeline_virtual_stages < 2:
                raise ValueError(
                    f"--pipeline_schedule=interleaved needs "
                    f"--pipeline_virtual_stages >= 2, got "
                    f"{FLAGS.pipeline_virtual_stages}")
            if FLAGS.pipeline_microbatches % FLAGS.pipeline_parallel:
                raise ValueError(
                    f"--pipeline_schedule=interleaved needs "
                    f"--pipeline_microbatches "
                    f"({FLAGS.pipeline_microbatches}) divisible by "
                    f"--pipeline_parallel ({FLAGS.pipeline_parallel})")
        if FLAGS.tensor_parallel > 1:
            raise ValueError(
                "--pipeline_parallel with --tensor_parallel is not supported")
        if FLAGS.steps_per_call > 1 or FLAGS.grad_accum_steps > 1:
            raise ValueError(
                "--pipeline_parallel already microbatches internally; it is "
                "exclusive with --steps_per_call/--grad_accum_steps")
        if FLAGS.bert_dropout > 0:
            raise ValueError(
                "--bert_dropout with --pipeline_parallel is unsupported "
                "(the pipelined stage schedule is rng-free)")
        if FLAGS.sequence_parallel > 1 or FLAGS.attention_backend in (
                "ring", "ulysses"):
            raise ValueError(
                "--pipeline_parallel cannot nest sequence-parallel attention "
                "(--sequence_parallel/--attention_backend=ring|ulysses): "
                "shard_map inside shard_map is unsupported")
        if getattr(FLAGS, "gpt_matmul_int8", False):
            raise ValueError(
                "--gpt_matmul_int8 with --pipeline_parallel is not wired "
                "up; drop one of the two flags")
    if FLAGS.expert_parallel > 1:
        # Fail with a flag-level message rather than an opaque GSPMD
        # divisibility error deep inside device_put.
        if FLAGS.model != "bert_moe":
            raise ValueError(
                f"--expert_parallel={FLAGS.expert_parallel} needs an MoE "
                f"model (--model=bert_moe), got --model={FLAGS.model}")
        if FLAGS.num_experts % FLAGS.expert_parallel:
            raise ValueError(
                f"--num_experts={FLAGS.num_experts} must be divisible by "
                f"--expert_parallel={FLAGS.expert_parallel}")

    cluster = ClusterSpec({"ps": FLAGS.ps_hosts, "worker": FLAGS.worker_hosts})
    num_workers = cluster.num_workers
    # Async workers are single-controller BY DESIGN: each runs its own
    # lockstep-free program on its own devices and exchanges through the
    # control plane at its own cadence.  Joining them into one
    # multi-controller mesh (the sync sharded-feed path) would make every
    # local step part of one SPMD program — the moment cadences diverge
    # (one worker finishes or stalls) the others deadlock in a collective
    # that never completes.  This mirrors the reference's async mode, where
    # workers only ever met at the PS, never at each other
    # (``distributed.py:102,145``).
    init_distributed = None  # TpuServer's default policy (sync multi-host)
    if FLAGS.job_name == "worker" and not FLAGS.sync_replicas:
        init_distributed = False
    server = TpuServer(cluster, FLAGS.job_name, FLAGS.task_index,
                       initialize_distributed=init_distributed,
                       heartbeat_timeout=FLAGS.heartbeat_timeout,
                       kv_persist_path=os.path.join(
                           FLAGS.logdir, "coordination_kv.journal"),
                       coord_instances=FLAGS.coord_instances,
                       coord_standbys=FLAGS.coord_standbys or None)
    if FLAGS.job_name == "ps":
        server.join()
        return

    chief = is_chief(FLAGS.task_index)
    # Late-bound elastic-membership context: the masked-sync replica mask
    # closure reads the watcher from here once it exists (the watcher is
    # built after the supervisor, the mask fn before it).
    elastic_ctx: dict = {"watcher": None}
    # One declarative layout for the whole run (docs/autotune.md): the
    # CLI flags resolve into a ParallelConfig — or a tuned profile
    # supplies one wholesale (its data axis pinned to the tuned size) —
    # and mesh + batch sharding + state placement all derive from it.
    pcfg = (profile_pcfg if profile_pcfg is not None
            else mesh_lib.ParallelConfig.from_flags(FLAGS))
    mesh = pcfg.build_mesh()
    num_replicas = mesh_lib.num_replicas(mesh)

    # Model init may trace attention (flax init runs the forward); give the
    # ring backend its mesh for the whole build.
    from .ops.attention import attention_mesh
    with attention_mesh(mesh):
        bundle = registry.build(FLAGS.model, FLAGS, mesh=mesh)
    if FLAGS.trainable_params:
        # Selective fine-tuning: wrap the model's optimizer so only matching
        # params train, and re-init the slots from the wrapped transform
        # (frozen params then carry no slot memory at all).
        from .training.optimizers import freeze_except
        tx, n_train, n_total = freeze_except(
            bundle.state.tx, bundle.state.params, FLAGS.trainable_params)
        bundle.state = bundle.state.replace(
            tx=tx, opt_state=tx.init(bundle.state.params))
        print(f"Worker {FLAGS.task_index}: --trainable_params="
              f"{FLAGS.trainable_params!r} trains {n_train:,} of "
              f"{n_total:,} parameters")
    use_tp = (bundle.sharding_rules is not None
              and (mesh.shape[mesh_lib.MODEL_AXIS] > 1
                   or mesh.shape[mesh_lib.EXPERT_AXIS] > 1))
    if FLAGS.ema_decay > 0:
        if bundle.stateful_loss_fn is not None or FLAGS.pipeline_parallel > 1:
            raise ValueError(
                "--ema_decay supports the plain/scanned/accumulating sync "
                "steps only (not stateful models or pipeline mode)")
        # Seed the average at a COPY of the initial weights (aliasing the
        # same buffers would make donation see the same argument twice);
        # placement below covers it.
        bundle.state = bundle.state.replace(
            ema_params=jax.tree.map(lambda x: x.copy(), bundle.state.params))

    if FLAGS.fsdp:
        if bundle.place_state is not None or FLAGS.pipeline_parallel > 1:
            raise ValueError(
                "--fsdp is incompatible with models that own their placement "
                "(--pipeline_parallel stages shard over the 'pipe' axis)")
        # use_tp and stateful models force the sync path below even when
        # --sync_replicas=false, so only a genuinely-async TRAINING run is
        # rejected (eval mode only restores the placed state).
        if (FLAGS.mode == "train" and not FLAGS.sync_replicas
                and num_replicas > 1 and not use_tp
                and bundle.stateful_loss_fn is None):
            raise ValueError(
                "--fsdp requires sync mode: async replicas hold independent "
                "full parameter copies by design")
    if bundle.place_state is not None:
        state = bundle.place_state(mesh, bundle.state)
    else:
        # The declarative layout's placement dispatch (fsdp -> TP rules
        # -> replicate), parity-pinned against the historical ad-hoc
        # branches in tests/test_mesh_config.py.
        state = pcfg.place_state(mesh, bundle.state, bundle.sharding_rules)
    if FLAGS.log_sharding:
        from .parallel.sharding import path_str

        def _log_placement(path, leaf):
            spec = getattr(leaf.sharding, "spec", leaf.sharding)
            print(f"Worker {FLAGS.task_index}: param {path_str(path)} "
                  f"{tuple(leaf.shape)} -> {spec}")
        jax.tree_util.tree_map_with_path(_log_placement, state.params)

    datasets = bundle.load_datasets(FLAGS.data_dir)
    if FLAGS.feed_dtype == "uint8":
        # Gate on the data itself (unit-scale float image splits), not a
        # model-name list — a newly registered image model works untouched.
        import numpy as np
        images = getattr(datasets.train, "images", None)
        if not (isinstance(images, np.ndarray)
                and images.dtype == np.float32):
            raise ValueError(
                f"--feed_dtype=uint8 applies to the image models "
                f"(float image pipelines); --model={FLAGS.model} feeds "
                f"{type(datasets.train).__name__} batches")
        from .data.datasets import uint8_feed
        datasets = uint8_feed(datasets)
    eval_fn = bundle.make_eval_fn()
    if FLAGS.ema_decay > 0:
        # Evaluate the averaged weights (validation AND the final test).
        _raw_eval = eval_fn
        def eval_fn(st, split, _base=_raw_eval):
            return _base(st.replace(params=st.ema_params), split)

    if FLAGS.mode == "eval":
        # Evaluation-only entry: restore the newest checkpoint into the same
        # placed state the training run would build (TP/pipeline/EMA layouts
        # included — the restore template is the placed state itself), then
        # report validation + test accuracy in the reference's output shape.
        with attention_mesh(mesh):
            sv = Supervisor(
                is_chief=True, logdir=os.path.join(FLAGS.logdir, bundle.name),
                init_fn=lambda: state)
            try:
                if sv.latest_step() is None:
                    print(f"WARNING: no checkpoint found under "
                          f"{os.path.join(sv.logdir, 'checkpoints')}; "
                          "evaluating the fresh initialization")
                try:
                    state = sv.prepare_or_wait_for_state()
                except ValueError as e:
                    raise ValueError(
                        "--mode=eval could not restore the checkpoint: its "
                        "structure does not match the state this run's flags "
                        "build. Common causes: flags differing from the "
                        "training run (--optimizer, --ema_decay, "
                        "--trainable_params, model-size flags), or the run "
                        "trained async "
                        "(--sync_replicas=false), whose checkpoints store "
                        "per-replica parameter stacks eval mode does not "
                        "support — briefly resume in sync mode to write a "
                        "consensus checkpoint first") from e
                validation_accuracy = eval_fn(state, datasets.validation)
                test_accuracy = eval_fn(state, datasets.test)
            finally:
                sv.close()
                server.shutdown()
        restored_step = int(state.global_step)
        print(f"Worker {FLAGS.task_index}: restored global step {restored_step}")
        print(f"Worker {FLAGS.task_index}: validation accuracy "
              f"{validation_accuracy:g}")
        print(f"Worker {FLAGS.task_index}: test accuracy {test_accuracy:g}")
        return {"global_step": restored_step,
                "validation_accuracy": validation_accuracy,
                "test_accuracy": test_accuracy}

    stateful = bundle.stateful_loss_fn is not None
    use_pipe = FLAGS.pipeline_parallel > 1
    if use_pipe and not FLAGS.sync_replicas:
        print(f"Worker {FLAGS.task_index}: pipeline parallelism requires "
              "lockstep replicas; async mode unsupported — using sync.")
    if use_tp and not FLAGS.sync_replicas:
        print(f"Worker {FLAGS.task_index}: tensor parallelism requires "
              "lockstep replicas; async mode unsupported — using sync.")
    replica_mask_fn = None
    async_mode_active = False
    if FLAGS.sync_replicas or stateful or use_tp or use_pipe:
        # R is counted in *worker tasks* (reference distributed.py:92-99); each
        # task owns num_replicas/num_workers device replicas on the mesh.
        replicas_to_aggregate = sync_lib.resolve_replicas_to_aggregate(
            FLAGS.replicas_to_aggregate, num_workers)
        use_masked = (not stateful and not use_tp and not use_pipe
                      and replicas_to_aggregate < num_workers
                      and server.coordination_client is not None
                      and num_replicas % num_workers == 0)
        if use_masked and FLAGS.ema_decay > 0:
            raise ValueError(
                "--ema_decay with R<N masked sync is unsupported")
        if use_masked and FLAGS.fsdp:
            raise ValueError(
                "--fsdp with R<N masked sync is unsupported (the masked "
                "step's shard_map expects replicated parameters); use "
                "--replicas_to_aggregate equal to the worker count")
        if use_masked and FLAGS.steps_per_call > 1:
            raise ValueError(
                "--steps_per_call > 1 is incompatible with R<N masked sync "
                "(the replica mask is sampled per step)")
        if use_masked and bundle.needs_rng:
            raise ValueError(
                "--bert_dropout with R<N masked sync is unsupported; use "
                "--replicas_to_aggregate equal to the worker count")
        if FLAGS.log_grad_norm and (use_masked or stateful):
            # Best-effort observability: loud at startup, never fatal for a
            # workload (BatchNorm models / elastic masking) it can't cover.
            print(f"Worker {FLAGS.task_index}: --log_grad_norm is not "
                  "available on the "
                  + ("masked (R<N)" if use_masked else "stateful (BatchNorm)")
                  + " sync path — ignoring")
        if bundle.train_step_builder is not None:
            # Model supplies its own step (the 1F1B pipeline's hand-rolled
            # backward cannot be built from loss_fn alone).
            if FLAGS.log_grad_norm:
                print(f"Worker {FLAGS.task_index}: --log_grad_norm is not "
                      "available on the 1F1B pipeline step — ignoring")
            train_step = bundle.train_step_builder(mesh)
        elif use_masked:
            # R<N straggler-drop: per-task health bits (cached by a background
            # poller — no TCP on the hot path) expanded to per-device replicas.
            # Health excludes both dead workers (heartbeat timeout) and — with
            # --straggler_lag — slow-but-alive workers behind the front-runner
            # (progress rides the heartbeats; see coord.cc Health()).
            # With elastic membership active, the mask is additionally ANDed
            # with the membership watcher's active set: membership says who
            # BELONGS to the replica set this epoch (a LEAVE shrinks it
            # immediately, no lease wait), health says who is answering.
            import numpy as np
            coord = server.coordination_client
            devices_per_task = num_replicas // num_workers
            coord.start_health_polling(interval=1.0, num_tasks=num_workers,
                                       straggler_lag=FLAGS.straggler_lag)
            train_step = sync_lib.build_masked_sync_train_step(
                mesh, bundle.loss_fn)
            last_mask = [None]
            mask_progress = {"base": 0, "n": 0}
            def replica_mask_fn():
                mask_progress["n"] += 1
                coord.set_progress(mask_progress["base"] + mask_progress["n"])
                watcher = elastic_ctx.get("watcher")
                mask = sync_lib.replica_mask_from_tasks(
                    coord.cached_health(), num_workers, devices_per_task,
                    members=(watcher.active_mask(num_workers)
                             if watcher is not None else None))
                if (last_mask[0] is None
                        or not np.array_equal(mask, last_mask[0])):
                    # Observable straggler-drop (the reference's only signal
                    # was silence); printed once per live-set change.
                    print(f"Worker {FLAGS.task_index}: live replica mask "
                          f"{mask.astype(int).tolist()}")
                    last_mask[0] = mask.copy()
                return mask
        elif stateful:
            if not FLAGS.sync_replicas:
                print(f"Worker {FLAGS.task_index}: model {FLAGS.model} has "
                      "non-trainable state; async mode unsupported — using sync.")
            if FLAGS.steps_per_call > 1:
                train_step = sync_lib.build_scanned_stateful_sync_train_step(
                    mesh, bundle.stateful_loss_fn,
                    num_steps=FLAGS.steps_per_call)
            else:
                train_step = sync_lib.build_stateful_sync_train_step(
                    mesh, bundle.stateful_loss_fn)
        elif FLAGS.steps_per_call > 1:
            train_step = sync_lib.build_scanned_sync_train_step(
                mesh, bundle.loss_fn, num_steps=FLAGS.steps_per_call,
                needs_rng=bundle.needs_rng, ema_decay=FLAGS.ema_decay,
                log_grad_norm=FLAGS.log_grad_norm)
        elif FLAGS.grad_accum_steps > 1:
            train_step = sync_lib.build_accumulating_sync_train_step(
                mesh, bundle.loss_fn, accum_steps=FLAGS.grad_accum_steps,
                needs_rng=bundle.needs_rng, ema_decay=FLAGS.ema_decay,
                log_grad_norm=FLAGS.log_grad_norm)
        else:
            train_step = sync_lib.build_sync_train_step(
                mesh, bundle.loss_fn, needs_rng=bundle.needs_rng,
                ema_decay=FLAGS.ema_decay,
                log_grad_norm=FLAGS.log_grad_norm)
    else:
        if FLAGS.ema_decay > 0:
            raise ValueError("--ema_decay requires sync mode")
        if (FLAGS.steps_per_call > 1
                and FLAGS.steps_per_call != FLAGS.async_sync_period):
            raise ValueError(
                f"--steps_per_call={FLAGS.steps_per_call} in async mode must "
                f"equal --async_sync_period={FLAGS.async_sync_period}: each "
                "dispatch scans one full sync period (local steps + merge)")
        if FLAGS.grad_accum_steps > 1:
            raise ValueError(
                "--grad_accum_steps > 1 requires sync mode")
        if bundle.needs_rng:
            raise ValueError(
                "--bert_dropout requires sync mode (async replica steps "
                "are rng-free)")
        if FLAGS.log_grad_norm:
            raise ValueError(
                "--log_grad_norm requires sync mode (async replicas step "
                "independently; there is no single global gradient)")
        from .parallel.async_replicas import (
            build_async_train_step, build_scanned_async_train_step,
            merge_params_tree)
        async_mode_active = True
        if FLAGS.steps_per_call > 1:
            # One dispatch = sync_period collective-free local steps + one
            # merge (the scanned async step) — amortized host dispatch.
            train_step, state = build_scanned_async_train_step(
                mesh, bundle.loss_fn, state,
                sync_period=FLAGS.async_sync_period)
        else:
            train_step, state = build_async_train_step(
                mesh, bundle.loss_fn, state,
                sync_period=FLAGS.async_sync_period)
        # Async state stacks per-replica params; evaluate the consensus mean.
        base_eval = eval_fn
        def eval_fn(astate, split, _base=base_eval):
            merged = astate.replace(params=merge_params_tree(astate.params))
            return _base(merged, split)

    coord = server.coordination_client
    if coord is not None:
        from .cluster.coordination import CoordinationError
        try:
            # Single-worker runs shouldn't hang on an absent coordinator: the
            # reference's config #1 ("1 host, no PS" north star) must work
            # standalone.  Multi-worker bring-up keeps the long poll.
            coord.register(timeout=5.0 if num_workers == 1 else 120.0)
            coord.start_heartbeats()
            if coord.restarts:
                # The worker-rejoin path (docs/fault_tolerance.md): the
                # coordinator has seen earlier incarnations of this task id —
                # this process is a restarted worker re-entering the run; the
                # Supervisor below restores the last good checkpoint.
                print(f"Worker {FLAGS.task_index}: rejoined coordination "
                      f"service (restart #{coord.restarts}); restoring from "
                      "the last good checkpoint")
        except CoordinationError:
            if num_workers > 1:
                raise
            print(f"Worker {FLAGS.task_index}: no coordination service at "
                  f"{cluster.coordinator_address}; running standalone.")
            coord.close()
            coord = None

    if chief:
        print(f"Worker {FLAGS.task_index}: Initailizing session...")
    else:
        print(f"Worker {FLAGS.task_index}: Waiting for session to be initaialized...")

    init_state = state
    # Namespace checkpoints per model: a shared logdir must never restore one
    # model's tree into another's (orbax structure mismatch at startup).
    sv = Supervisor(
        is_chief=chief, logdir=os.path.join(FLAGS.logdir, bundle.name),
        init_fn=lambda: init_state,
        recovery_wait_secs=1,
        save_interval_steps=FLAGS.save_interval_steps,
        coordination_client=coord,
        max_to_keep=FLAGS.max_checkpoints_to_keep,
    )
    state = sv.prepare_or_wait_for_state()
    print(f"Worker {FLAGS.task_index}: Session initialization  complete.")
    if replica_mask_fn is not None:
        # Progress heartbeats count from the restored step so a rejoining
        # worker isn't misclassified as a straggler while it resumes.
        mask_progress["base"] = int(state.global_step)

    # Elastic membership (docs/fault_tolerance.md): resolve the mode, then
    # mirror the coordination service's (epoch, active set) into this
    # process and react to resizes instead of stalling behind the dead.
    elastic_mode = FLAGS.elastic_mode
    if elastic_mode not in ("auto", "off", "in_place", "reshard"):
        raise ValueError(f"--elastic_mode must be auto, off, in_place or "
                         f"reshard, got {elastic_mode!r}")
    if elastic_mode == "auto":
        if (replica_mask_fn is not None and coord is not None
                and jax.process_count() == 1):
            elastic_mode = "in_place"   # masked R<N sync: flip the mask
        elif (jax.process_count() > 1 and coord is not None
              and FLAGS.sync_replicas):
            # Fixed XLA topology: save + resize.  This also covers masked
            # multi-controller runs — an in-place pause/restore of one
            # lockstep process would deadlock the others' collectives.
            elastic_mode = "reshard"
        else:
            elastic_mode = "off"
    elastic_controller = None
    if elastic_mode != "off":
        if coord is None:
            raise ValueError(
                f"--elastic_mode={FLAGS.elastic_mode} needs a coordination "
                "service (standalone runs have no membership to watch)")
        from .cluster.coordination import MembershipWatcher
        from .training.elastic import ElasticController
        elastic_watcher = MembershipWatcher(coord, num_workers, interval=1.0)
        elastic_watcher.start()
        elastic_ctx["watcher"] = elastic_watcher
        elastic_controller = ElasticController(
            watcher=elastic_watcher, client=coord,
            task_index=FLAGS.task_index, num_workers=num_workers,
            supervisor=sv, mode=elastic_mode, is_chief=chief,
            reshard_margin_steps=FLAGS.elastic_reshard_margin)
        print(f"Worker {FLAGS.task_index}: elastic membership active "
              f"(mode={elastic_mode})")

    _finalize_async = None
    averager = None
    if (async_mode_active and num_workers > 1 and coord is not None
            and jax.process_count() == 1):
        # Cross-process Hogwild-style exchange: independent cadences, bounded
        # staleness, parameters durable on the coordination service (the
        # reference's PS role, SURVEY N2/N4) — see cluster/param_sync.py.
        # Single-controller processes only: in multi-controller runs the
        # replicas already share one global mesh (lockstep local-SGD), and
        # host-side access to non-addressable global arrays would break the
        # cross-process dispatch order.
        from .cluster.coordination import CoordinationError
        from .cluster.param_sync import (CompressedShardedAverager,
                                         HierarchicalCompressedAverager,
                                         ParamAverager, run_namespace)
        from .parallel.async_replicas import (adopt_consensus,
                                              adopt_consensus_delta)
        # The binary side-channel lives next to the checkpoints — same
        # shared-FS assumption — so transformer-scale trees exchange at
        # disk bandwidth instead of base64-through-one-socket.
        _avg_kwargs = dict(
            namespace=run_namespace(FLAGS.logdir),
            exchange_dir=os.path.join(FLAGS.logdir, "async_exchange"))
        if FLAGS.async_compress not in ("off", "int8", "bf16"):
            raise ValueError(f"--async_compress must be off, int8 or bf16, "
                             f"got {FLAGS.async_compress!r}")
        if FLAGS.async_compress != "off":
            # Compressed sharded exchange (docs/param_exchange.md): shard
            # ownership is keyed on the coordination service's membership
            # epoch so every worker derives the same owner map; a worker
            # evicted mid-round stops owning its shard at the next epoch.
            def _members_view(_coord=coord):
                return _coord.members()

            from .parallel.sync import auto_slice_size
            slice_size = (FLAGS.slice_size if FLAGS.slice_size > 0
                          else auto_slice_size(num_workers,
                                               FLAGS.dcn_data_parallel))
            if slice_size > 1:
                # Hierarchical exchange (docs/param_exchange.md,
                # "Hierarchical exchange"): raw intra-slice reduction, one
                # quantized inter-slice shard exchange per slice exporter.
                averager = HierarchicalCompressedAverager(
                    coord, FLAGS.task_index, num_workers,
                    quant=FLAGS.async_compress,
                    block=FLAGS.async_quant_block,
                    anchor_every=FLAGS.async_anchor_every,
                    epoch_fn=_members_view, slice_size=slice_size,
                    **_avg_kwargs)
                print(f"Worker {FLAGS.task_index}: hierarchical "
                      f"compressed exchange on (slice_size={slice_size}, "
                      f"delta+{FLAGS.async_compress} inter-slice shard "
                      f"reduce, anchor every {FLAGS.async_anchor_every} "
                      f"rounds)")
            else:
                averager = CompressedShardedAverager(
                    coord, FLAGS.task_index, num_workers,
                    quant=FLAGS.async_compress,
                    block=FLAGS.async_quant_block,
                    anchor_every=FLAGS.async_anchor_every,
                    epoch_fn=_members_view, **_avg_kwargs)
                print(f"Worker {FLAGS.task_index}: compressed parameter "
                      f"exchange on (delta+{FLAGS.async_compress} sharded "
                      f"reduce, anchor every {FLAGS.async_anchor_every} "
                      f"rounds)")
        else:
            averager = ParamAverager(
                coord, FLAGS.task_index, num_workers, **_avg_kwargs)
        coord.start_health_polling(interval=1.0, num_tasks=num_workers)

        def _adopt(avg_tree, stacked_params):
            return adopt_consensus(stacked_params, avg_tree)

        # Restart-and-rejoin: adopt the collective's published state instead
        # of starting from scratch (the PS-durability behavior).
        try:
            latest = averager.pull_latest(merge_params_tree(state.params))
        except (CoordinationError, OSError):
            latest = None
        if latest is not None:
            state = state.replace(params=_adopt(latest, state.params))
            print(f"Worker {FLAGS.task_index}: adopted published collective "
                  "parameters from the coordination service")

        _base_async_step = train_step
        # With the scanned async step each call already covers a full sync
        # period of local steps, so exchange every call.
        _period = (1 if FLAGS.steps_per_call > 1
                   else max(FLAGS.async_sync_period, 1))
        _calls = {"n": 0}

        if FLAGS.async_overlap_exchange:
            # Background-threaded exchange (VERDICT r4 #5): the GB-scale
            # publish/fetch/average runs while training continues; the
            # consensus lands one period late as a DELTA against the
            # snapshot it was computed from, preserving the local steps
            # taken meanwhile (cluster/param_sync.OverlappedAverager).
            from .cluster.param_sync import OverlappedAverager
            import numpy as _np
            overlapped = OverlappedAverager(
                averager, alive_fn=coord.cached_health)

            def _adopt_delta(avg_tree, snap_tree, stacked_params):
                return adopt_consensus_delta(stacked_params, avg_tree,
                                             snap_tree)

            def _apply_ready(s, result):
                avg, snap, peers = result
                if peers:
                    s = s.replace(params=_adopt_delta(avg, snap, s.params))
                    secs = overlapped.last_exchange_seconds
                    print(f"Worker {FLAGS.task_index}: applied overlapped "
                          f"average with {peers} peer(s) at local step "
                          f"{_calls['n']} (exchange ran {secs:.1f}s in "
                          f"background, {averager.last_publish_transport} "
                          "publish)")
                return s

            def _exchange_cb(s):
                result = overlapped.poll()
                if result is not None:
                    s = _apply_ready(s, result)
                if not overlapped.busy:
                    # Snapshot ONLY when the thread can take it — the
                    # device-to-host copy of a GB tree is itself the
                    # stall being hidden.
                    overlapped.submit(jax.tree.map(
                        lambda x: _np.ascontiguousarray(_np.asarray(x)),
                        merge_params_tree(s.params)))
                return s

            def _finalize_async(s):
                """End of training: collect the in-flight exchange so the
                final (checkpointed/evaluated) params carry the last
                consensus pull, then stop the thread."""
                result = overlapped.drain(timeout=60.0)
                if result is not None:
                    s = _apply_ready(s, result)
                overlapped.close()
                return s
        else:
            def _exchange_cb(s):
                try:
                    avg, peers = averager.exchange(
                        merge_params_tree(s.params),
                        alive=coord.cached_health())
                except (CoordinationError, OSError):
                    # Never let a control-plane hiccup, a shared-FS error
                    # (binary side-channel), or an oversize payload kill
                    # training: async workers must not depend on peers —
                    # skip this exchange and keep stepping.
                    print(f"Worker {FLAGS.task_index}: parameter exchange "
                          "failed (coordination unreachable); continuing")
                    return s
                if peers:
                    s = s.replace(params=_adopt(avg, s.params))
                    print(f"Worker {FLAGS.task_index}: averaged parameters "
                          f"with {peers} peer(s) at local step "
                          f"{_calls['n']} "
                          f"({averager.last_publish_transport} publish, "
                          f"{averager.last_publish_mb_per_sec:.0f} MB/s)")
                return s

        def train_step(s, batch, _base=_base_async_step):
            s, m = _base(s, batch)
            _calls["n"] += 1
            if _calls["n"] % _period == 0:
                s = _exchange_cb(s)
            return s, m

    if FLAGS.inject_step_delay:
        # Fault injection (SURVEY §5 names the reference's lack of it): slow
        # this worker down for a window of local steps so straggler handling
        # (--straggler_lag exclusion and rejoin) can be exercised end to end.
        import time as _time
        _windows = []
        try:
            for spec in FLAGS.inject_step_delay.split(","):
                parts = spec.split(":")
                if len(parts) == 2:
                    _windows.append((float(parts[0]), 0, int(parts[1])))
                elif len(parts) == 3:
                    _windows.append(
                        (float(parts[0]), int(parts[1]), int(parts[2])))
                else:
                    raise ValueError(parts)
        except ValueError:
            raise ValueError(
                f"--inject_step_delay windows must be 'SECS:N' or "
                f"'SECS:START:END', got {FLAGS.inject_step_delay!r}") from None
        _fault = {"n": 0}
        _inner_step = train_step

        def train_step(*args, _inner=_inner_step):
            out = _inner(*args)
            i = _fault["n"]
            _fault["n"] += 1
            delay = sum(d for d, lo, hi in _windows if lo <= i < hi)
            if delay > 0:
                _time.sleep(delay)
            return out

    stacked = FLAGS.steps_per_call > 1 or FLAGS.grad_accum_steps > 1
    batch_sharding = pcfg.batch_sharding(mesh, stacked=stacked)
    log_every, validation_every = FLAGS.log_every, FLAGS.validation_every
    if FLAGS.steps_per_call > 1:
        # Chunked stepping can only log/validate at chunk boundaries; round
        # the cadences up so the default flags work out of the box.
        k = FLAGS.steps_per_call
        rounded = tuple(((n + k - 1) // k) * k if n else 0
                        for n in (log_every, validation_every))
        if rounded != (log_every, validation_every):
            print(f"Worker {FLAGS.task_index}: rounding log_every "
                  f"{log_every}->{rounded[0]}, validation_every "
                  f"{validation_every}->{rounded[1]} to --steps_per_call={k} "
                  "chunk boundaries")
            log_every, validation_every = rounded
    metrics_path = FLAGS.metrics_file
    if metrics_path and num_workers > 1:
        # One file per process: concurrent appends to a shared file can
        # interleave mid-line, and records would be unattributable.
        metrics_path = f"{metrics_path}.task{FLAGS.task_index}"
    metrics_logger = MetricsLogger(
        metrics_path, static_fields={"worker": FLAGS.task_index})

    # Unified run telemetry (docs/observability.md): one kind-tagged JSONL
    # stream per host carrying the step-time breakdown, live MFU (priced
    # by tools/cost_model.py), HBM watermarks, and cluster
    # health — everything tools/summarize_run.py needs for a run report.
    telemetry = None
    health_reporter = None
    if metrics_path and FLAGS.telemetry:
        import numpy as _np
        from .tools import cost_model
        from .utils.telemetry import SCHEMA_VERSION, Telemetry
        # Count on the bundle's tree: the live state may be per-replica
        # stacked (async mode), which would inflate the FLOP model.
        n_params = sum(int(_np.prod(p.shape))
                       for p in jax.tree.leaves(bundle.state.params))
        # Tokens per optimizer step: rows for classifiers, B*S for LMs.
        # One device dispatch covers steps_per_call optimizer steps (or
        # accum_steps microbatches), but MFU is per *optimizer step rate*,
        # which the rate meter already counts in optimizer steps.
        seq_tokens = FLAGS.model in ("bert_tiny", "bert_moe", "gpt_mini")
        tokens = FLAGS.batch_size * (FLAGS.bert_seq_len if seq_tokens else 1)
        if FLAGS.model == "gpt_mini":
            from .models import gpt as _gpt_lib
            _cfg = _gpt_lib.mini()
            flops_per_step = cost_model.train_step_flops(
                n_params, tokens, num_layers=_cfg.num_layers,
                hidden_size=_cfg.hidden_size, seq_len=FLAGS.bert_seq_len,
                window=FLAGS.attention_window)
        else:
            flops_per_step = cost_model.train_step_flops(n_params, tokens)
        if FLAGS.grad_accum_steps > 1:
            # Each optimizer step consumed accum_steps microbatches.
            flops_per_step *= FLAGS.grad_accum_steps
        peak = (FLAGS.peak_tflops * 1e12 * jax.device_count()
                if FLAGS.peak_tflops > 0
                else cost_model.device_peak_flops())
        telemetry = Telemetry(metrics_logger, flops_per_step=flops_per_step,
                              peak_flops_per_sec=peak)
        # Crash flight recorder (docs/observability.md): the bus keeps a
        # constant-memory ring of recent records and dumps it next to the
        # stream when this process is about to die (SIGTERM below, chaos
        # kill_at_step via the injector hook, fatal loop exception).
        telemetry.enable_flight_recorder(metrics_path + ".flight")
        # Distributed tracing: spans from the loop, prefetch producers,
        # and the coordination client flow into the same stream; the run
        # id (shared — derived from the logdir every worker was launched
        # with) keys the cross-worker trace_id correlation.
        from .utils import tracing as tracing_lib
        run_id = os.path.basename(os.path.normpath(FLAGS.logdir)) or "run"
        tracing_lib.install(tracing_lib.Tracer(telemetry, run_id=run_id))
        # Recovery/fault events join the same stream: the supervisor flushes
        # any checkpoint-fallback events its restore already recorded, an
        # armed chaos injector tags the faults it fires, and a rejoining
        # incarnation announces itself as a kind="recovery" record.
        sv.attach_telemetry(telemetry)
        if averager is not None:
            # Exchange observability (docs/param_exchange.md): per-period
            # kind="param_exchange" records (bytes-on-wire, compression
            # ratio, quantization residual norm) plus the exchange_bytes/
            # exchange_ratio gauges the loop folds into the live STATPUT
            # summary — a misconfigured (uncompressed) worker shows up in
            # watch_run, not just in a post-mortem.
            averager.attach_telemetry(telemetry)
        if elastic_controller is not None:
            # Resize telemetry (elastic_shrink/elastic_grow/...) joins the
            # stream, keyed on the heartbeat-carried progress step.
            elastic_controller.attach_telemetry(telemetry)
            elastic_ctx["watcher"].set_step_fn(
                lambda: max(coord._progress_step, 0))
        if faults.active() is not None:
            faults.active().attach_telemetry(telemetry)
        if coord is not None and coord.restarts:
            telemetry.emit("recovery", step=int(state.global_step),
                           action="rejoin", restarts=coord.restarts)
        telemetry.emit(
            "run_meta",
            schema_version=SCHEMA_VERSION,
            model=FLAGS.model, n_params=n_params,
            batch_size=FLAGS.batch_size, tokens_per_step=tokens,
            flops_per_step=flops_per_step, peak_flops_per_sec=peak,
            device_kind=jax.devices()[0].device_kind,
            n_devices=jax.device_count(),
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            steps_per_call=FLAGS.steps_per_call,
            grad_accum_steps=FLAGS.grad_accum_steps)
        if coord is not None:
            # Control-plane timings (barrier waits) and periodic peer
            # health snapshots ride the same stream — stragglers and dead
            # workers become visible telemetry, not eventual timeouts.
            from .cluster.coordination import (ClusterHealthReporter,
                                               CoordinationError)
            coord.attach_telemetry(telemetry)
            # Clock alignment for the cross-worker trace: estimate this
            # host's offset to the coordination server (NTP-style midpoint
            # over K TIME samples) and stamp it into the stream;
            # tools/export_trace.py applies it so one worker's spans line
            # up against another's to within the measured RTT.
            try:
                offset_s, rtt_s = coord.clock_offset()
                telemetry.emit(
                    "clock_sync", step=0,
                    offset_ms=round(offset_s * 1000.0, 3),
                    rtt_ms=round(rtt_s * 1000.0, 3),
                    t_unix=round(time.time(), 6), source="coord_time")
            except CoordinationError:
                pass  # no alignment beats no run; export falls back to 0
            if FLAGS.health_report_every > 0:
                health_reporter = ClusterHealthReporter(
                    coord, telemetry, num_tasks=num_workers,
                    interval=FLAGS.health_report_every,
                    straggler_lag=FLAGS.straggler_lag)
                # Key records on the client's heartbeat-carried progress
                # step (never a device sync from a background thread).
                health_reporter.set_step_fn(
                    lambda: max(coord._progress_step, 0))
                health_reporter.start()
    stat_publish_fn = None
    if telemetry is not None and coord is not None:
        # Live watching (docs/observability.md): each logged step's compact
        # summary goes to the coordination server's stats ring (STATPUT) so
        # tools/watch_run.py can render the cluster mid-run without
        # touching any files.  Best-effort: no retry, failures swallowed.
        from .cluster.coordination import CoordinationError as _CoordErr

        def stat_publish_fn(payload, _coord=coord):
            try:
                _coord.stat_put(payload)
            except (_CoordErr, ValueError):
                pass

    summary_writer = (SummaryWriter(FLAGS.summary_dir)
                      if FLAGS.summary_dir and chief else None)
    summary_ctx = summary_writer or contextlib.nullcontext()
    profile_ctx = (profiling.trace(FLAGS.profile_dir) if FLAGS.profile_dir
                   else contextlib.nullcontext())
    shutdown_ctx = (ShutdownSignal() if FLAGS.graceful_shutdown
                    else contextlib.nullcontext())
    # The ring backend builds its shard_map against the mesh at trace time;
    # a no-op context for every other backend.
    try:
        with attention_mesh(mesh), profile_ctx, metrics_logger, summary_ctx, \
                shutdown_ctx as shutdown:
            if shutdown is not None and telemetry is not None:
                # First line of the crash story: the moment SIGTERM/SIGINT
                # latches, the flight ring reaches disk — even if the
                # graceful checkpoint-and-exit path never gets to run.
                shutdown.add_callback(lambda: telemetry.dump_flight(
                    reason=f"signal:{shutdown.signal_name}"))
            state, result = run_training_loop(
                state=state,
                train_step=train_step,
                datasets=datasets,
                batch_size=FLAGS.batch_size,
                train_steps=FLAGS.train_steps,
                task_index=FLAGS.task_index,
                mesh=mesh,
                batch_sharding=batch_sharding,
                validation_every=validation_every,
                log_every=log_every,
                supervisor=sv,
                replica_mask_fn=replica_mask_fn,
                eval_fn=eval_fn,
                metrics_logger=metrics_logger,
                telemetry=telemetry,
                summary_writer=summary_writer,
                summary_histograms=FLAGS.summary_histograms,
                lr_fn=schedule_from_flags(FLAGS),
                steps_per_call=FLAGS.steps_per_call,
                accum_steps=FLAGS.grad_accum_steps,
                prefetch=FLAGS.prefetch,
                shutdown=shutdown,
                sharded_feed=FLAGS.sharded_feed,
                elastic=elastic_controller,
                stat_publish_fn=stat_publish_fn,
            )
    except BaseException as e:
        # Fatal exit: whatever killed the loop, the flight ring's last
        # records (the dying step's spans included) reach disk first.
        if telemetry is not None:
            telemetry.dump_flight(reason=f"fatal:{type(e).__name__}")
        raise
    finally:
        # Always reap the background health poller and membership watcher —
        # an exception out of the loop must not leak a thread that keeps
        # writing stale cluster_health records into the next run's stream.
        if health_reporter is not None:
            health_reporter.close()
        if elastic_ctx["watcher"] is not None:
            elastic_ctx["watcher"].close()
        if telemetry is not None:
            # The tracer is a process-wide global; a second run in this
            # process (tests drive main() repeatedly) must not write spans
            # into a closed stream.
            from .utils import tracing as _tracing
            _tracing.clear()
    if _finalize_async is not None:
        # Collect the in-flight background exchange so the persisted
        # params carry the last consensus pull (the in-loop final eval
        # already ran; bounded staleness covers the gap), and save it.
        state = _finalize_async(state)
        sv.maybe_save(state, force=True)
    sv.close()
    server.shutdown()
    return result


def cli() -> None:
    """Console-script entry point (``dtf-train``, see pyproject.toml)."""
    app.run(main)


if __name__ == "__main__":
    cli()
