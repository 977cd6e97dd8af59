"""InferenceService API types — KServe-analog serving specs.

Upstream shape (SURVEY.md §2.3; (U) kserve pkg/apis/serving/v1beta1):
``InferenceService{predictor{model{modelFormat,storageUri,runtime},
minReplicas,maxReplicas,scaleTarget,canaryTrafficPercent}, transformer,
explainer}`` plus ``ServingRuntime`` mapping modelFormat→runtime.

TPU-native differences: the predictor runtime is a JAX continuous-batching
engine (paged KV cache) rather than a container image; scaling unit is a
model-server process pinned to chips; canary is a traffic split between
generations of the same service.
"""

from __future__ import annotations

import enum
from typing import Any, Optional

from pydantic import (
    BaseModel, ConfigDict, Field, field_validator, model_validator,
)

from kubeflow_tpu.core.object import ApiObject, ConditionMixin
from kubeflow_tpu.core.registry import register_kind
from kubeflow_tpu.core.jobs import ParallelismSpec, TPUResourceSpec


class ModelFormat(str, enum.Enum):
    LLM = "llm"               # decoder LLM → continuous-batching engine
    ORBAX = "orbax"           # generic orbax checkpoint + registered model fn
    VISION = "vision"         # ViT/CLIP-style encoder
    CUSTOM = "custom"         # user-registered Model class


class ModelSpec(BaseModel):
    model_config = ConfigDict(extra="forbid", protected_namespaces=())

    model_format: ModelFormat = ModelFormat.LLM
    # file:///ckpt-dir, artifact://<digest>|<name>[@<ver>] (the platform
    # artifact store — pipeline-published models), random:// (fresh init).
    storage_uri: Optional[str] = None
    runtime: Optional[str] = None       # explicit ServingRuntime name
    model_name: Optional[str] = None    # name exposed on the protocol surface
    config: dict[str, Any] = Field(default_factory=dict)  # model arch/config


class SpeculativeSpec(BaseModel):
    """Speculative decoding knobs (≈ vLLM ``speculative_config``).

    Greedy requests draft up to ``k`` tokens per decode round and verify all
    of them in ONE batched dispatch — multiple verified tokens per dispatch
    at token-identical output (the decode hot path is dispatch- and
    HBM-bound, not FLOP-bound, so scoring k+1 positions costs barely more
    than scoring one). Draft sources:

    - ``ngram``: prompt/self lookup — match the last n-gram against the
      request's own prompt+generated tokens and propose the continuation
      that followed it (no extra model; wins on templated/repetitive
      suffixes: code, JSON, extraction, self-repeating generations).
    - ``draft_model``: a small decoder (``draft`` = {"preset", "overrides"})
      sharing the target's tokenizer/vocab runs ahead autoregressively;
      the target verifies. Wins on natural text where lookup misses.

    Sampling (temperature>0) requests fall back to the normal decode path —
    greedy verification is exact only for argmax decoding."""

    model_config = ConfigDict(extra="forbid")

    mode: str = "off"                # off | ngram | draft_model
    k: int = 4                       # draft tokens proposed per round
    # ngram mode: longest/shortest suffix n-gram to look up (tried in
    # descending order; longer matches are more specific, shorter ones
    # match earlier in the stream).
    ngram_max: int = 3
    ngram_min: int = 1
    # draft_model mode: the small decoder — {"preset": str,
    # "overrides": {...}} exactly like ModelSpec.config. Must share the
    # target's vocab (drafts are token ids).
    draft: dict[str, Any] = Field(default_factory=dict)

    @model_validator(mode="after")
    def _check(self) -> "SpeculativeSpec":
        if self.mode not in ("off", "ngram", "draft_model"):
            raise ValueError(
                f"unknown speculative mode {self.mode!r}; "
                "one of off|ngram|draft_model")
        if self.mode != "off" and not (1 <= self.k <= 64):
            raise ValueError("speculative.k must be in [1, 64]")
        if self.mode == "ngram" and not (
                1 <= self.ngram_min <= self.ngram_max):
            raise ValueError("need 1 <= ngram_min <= ngram_max")
        return self


class LoRASpec(BaseModel):
    """Multi-tenant LoRA serving knobs (serve/lora.py): one engine
    serves up to ``max_adapters`` rank-``rank`` adapters over shared
    base weights, hot-loading/evicting through the adapter registry.

    ``max_adapters`` sizes the PACKED device buffer (the slot count —
    also the fixed dispatch shape, so adapter churn never retraces);
    ``rank`` is the per-slot rank cap lower-rank adapters zero-pad to;
    ``targets`` names the attention projections the low-rank update
    applies to (wq/wk/wv/wo). ``max_adapters=0`` disables the subsystem
    — the engine then runs byte-for-byte the pre-LoRA dispatches."""

    model_config = ConfigDict(extra="forbid")

    max_adapters: int = 0
    rank: int = 8
    alpha: float = 16.0
    targets: tuple = ("wq", "wv")

    @model_validator(mode="after")
    def _check(self) -> "LoRASpec":
        if self.max_adapters < 0:
            raise ValueError("max_adapters must be >= 0")
        if self.max_adapters and not (1 <= self.rank <= 64):
            raise ValueError("lora.rank must be in [1, 64]")
        bad = set(self.targets) - {"wq", "wk", "wv", "wo"}
        if self.max_adapters and (bad or not self.targets):
            raise ValueError(
                f"lora.targets must be a non-empty subset of "
                f"wq/wk/wv/wo; got {self.targets}")
        return self


#: Multi-tenant QoS classes, highest priority first. The order IS the
#: policy: admission dequeues strictly by it, overload sheds from the
#: BACK of it (batch 429s before interactive ever does), and cross-class
#: preemption only ever evicts a strictly lower class.
QOS_CLASSES = ("interactive", "standard", "batch")

#: class name -> priority rank (lower = more urgent).
QOS_PRIORITY = {c: i for i, c in enumerate(QOS_CLASSES)}

#: Default class for requests that declare none (absent X-Kftpu-Qos
#: header / body field): the middle tier, so both "more urgent" and
#: "more sheddable" exist relative to it.
QOS_DEFAULT = "standard"


class QoSClassPolicy(BaseModel):
    """Per-class admission knobs. Unset fields inherit the engine-wide
    ``BatchingSpec.max_queue`` / ``queue_delay_budget`` behavior."""

    model_config = ConfigDict(extra="forbid")

    # Per-class admission quota: submit() sheds THIS class with 429 once
    # this many of its requests wait for a slot (0 = no class quota —
    # only the engine-wide bound applies). Lets a batch tenant's burst
    # hit its own ceiling long before it can crowd the shared queue.
    max_queue: int = 0
    # Per-class queue-delay budget (seconds): a request of this class
    # still waiting for a slot this long after arrival is shed
    # (finish_reason="shed"). None = the engine-wide budget.
    queue_delay_budget: Optional[float] = None

    @model_validator(mode="after")
    def _check(self) -> "QoSClassPolicy":
        if self.max_queue < 0:
            raise ValueError("max_queue must be >= 0")
        if self.queue_delay_budget is not None and self.queue_delay_budget <= 0:
            raise ValueError("queue_delay_budget must be positive")
        return self


class QoSSpec(BaseModel):
    """Multi-tenant scheduling policy for the engine: per-class admission
    quotas/budgets plus cross-class recompute preemption. Class priority
    itself is fixed (``QOS_CLASSES`` order) — the spec tunes how hard each
    tier is protected, not who outranks whom."""

    model_config = ConfigDict(extra="forbid")

    classes: dict[str, QoSClassPolicy] = Field(default_factory=dict)
    # Cross-class preemption: an arriving higher-class request may
    # recompute-preempt the youngest slot of the lowest running class
    # (vLLM-style recompute via the engine's preempted lane). False
    # limits preemption to the existing page-pressure path.
    preemption: bool = True

    @model_validator(mode="after")
    def _check(self) -> "QoSSpec":
        unknown = set(self.classes) - set(QOS_CLASSES)
        if unknown:
            raise ValueError(
                f"unknown QoS classes {sorted(unknown)}; "
                f"known: {list(QOS_CLASSES)}")
        return self


#: Engine roles for disaggregated prefill/decode serving (the
#: DistServe/Splitwise motif, TPU-native). ``unified`` is the classic
#: engine; ``prefill`` runs prompt chunks, samples the FIRST token, and
#: exports the slot's KV as a paged handoff instead of decoding;
#: ``decode`` adopts handed-off KV into its own page pool and runs the
#: decode hot loop. Role specializes what a pool is USED for — every
#: role keeps the full engine machinery, so any replica can serve a
#: whole request locally (the unified-fallback path when a pool is
#: unhealthy).
ENGINE_ROLES = ("unified", "prefill", "decode")


class BatchingSpec(BaseModel):
    """Continuous-batching engine knobs (≈ vLLM engine args in the HF runtime)."""

    model_config = ConfigDict(extra="forbid")

    # Disaggregated serving role (ENGINE_ROLES). "prefill" engines stop
    # at the first token and export a KV handoff; "decode" engines adopt
    # handoffs; "unified" (default) is the classic single-engine path.
    role: str = "unified"
    max_batch_size: int = 8          # decode batch slots
    max_seq_len: int = 2048
    # The KV cache is a page pool (vLLM analog): HBM budget decoupled from
    # slots × max_seq_len; shared-prefix requests reuse pages. It is the
    # engine's only cache, so this is not an option: the key still parses
    # (manifests and the benchmark's traffic files carry ``paged: true``)
    # and its one legal value is True.
    paged: bool = True
    page_size: int = 128             # KV cache page (tokens)
    max_pages: Optional[int] = None  # default: slots × max_seq_len / page
    enable_prefix_caching: bool = True
    # Prefix-cache index (serve/kvtier.py). "radix" (default): token-block
    # radix tree over the page pool — live copy-on-write sharing of ref>0
    # prefix pages between in-flight requests, sub-page tail reuse (a
    # divergence allocates a fresh page and device-copies only the shared
    # partial block), and conversation re-use (a finished request's
    # prompt+output pages stay matchable). "flat" keeps the legacy
    # full-prompt chained-hash cache in PageAllocator (the A/B baseline).
    prefix_index: str = "radix"      # radix | flat
    # Host-RAM overflow tier (radix index only): cold sharer-free prefix
    # pages migrate device→host as raw page bytes on a background
    # migration thread and promote back on a radix hit before prefill
    # admits — long-idle conversations stop pinning HBM without losing
    # their recompute savings. Page budget of the host tier; 0 = off.
    host_kv_pages: int = 0
    # A cached (sharer-free) device page idle this long is demotion-
    # eligible; batched transfers move at most kv_migrate_batch_pages
    # per migration pass.
    kv_demote_after_s: float = 2.0
    kv_migrate_batch_pages: int = 32
    # Remote-storage third tier (fleet-wide KV fabric, serve/kvtier.py):
    # artifact-store root for KV spill blobs. Cold host-tier blobs idle
    # past kv_remote_after_s publish there (content-addressed + registry-
    # keyed by block chain), making a conversation's prefix resumable on
    # ANY replica after engine death or scale-down drain. None falls back
    # to $KFTPU_KV_REMOTE_ROOT; both unset = third tier off.
    remote_kv_root: Optional[str] = None
    kv_remote_after_s: Optional[float] = None  # default: 2× demote_after_s
    # Per-match remote promote/probe deadline: a slower store degrades
    # that admission to recompute instead of wedging it. None reads
    # $KFTPU_KV_REMOTE_DEADLINE_S (default 0.5).
    kv_remote_deadline_s: Optional[float] = None
    # Paged decode attention: "gather" (materialize pages, XLA attention —
    # 2× KV read), "pallas" (direct page reads via the paged-attention
    # kernel), or "auto" (pallas on TPU, gather elsewhere).
    paged_attn_impl: str = "auto"
    # Prompts prefill in chunks with decode interleaving; this many may
    # chunk concurrently (no head-of-line blocking between long prompts).
    # Where one chunk leaves the model's weights under-used, the chunks of
    # all of them go to the device as ONE program a scheduler pass
    # (serve/chunk_programs.py: ``plan_chunks``).
    max_concurrent_prefills: int = 2
    # Every admission prefills in chunks of this many tokens (a multiple
    # of page_size: chunk boundaries are page boundaries).
    chunked_prefill_tokens: int = 512
    # The most decode steps one device dispatch (a round) may run while
    # no prefill is in flight. A round is one program: sampling runs
    # on-device, the round's tokens reach the host together when it is
    # fetched, and a request that arrives waits behind the rounds in
    # flight. A CAP, not a length: with pipelined_decode the scheduler
    # dispatches the SHORTEST round whose device time hides the host's
    # own time an iteration, from what it measures of both
    # (serve/pacing.py): one step where the host is a few milliseconds
    # from the chip, up to this many where a dispatch costs more than a
    # step (a tunnel). 1 = always one step a dispatch.
    decode_steps: int = 32
    # The same cap WHILE a chunked prefill is in flight (the smaller of
    # the two binds): the prefill's next chunk and the running streams'
    # next tokens wait for the round, so this bounds both at this many
    # steps however slow the host is.
    prefill_interleave_steps: int = 8
    # Pipelined decode dispatch (hot-loop host-overhead elimination):
    # dispatch round N+1 before consuming round N's tokens, so
    # detokenization, stream callbacks, reaping and admission overlap
    # device compute instead of serializing behind a blocking device_get.
    # The scheduler's view is ONE ROUND STALE, bounded: admissions and
    # cancellations decided while a round is in flight take effect the
    # next round, and a cancelled slot's in-flight results are masked
    # before emission (output streams never contain post-cancel tokens).
    # Greedy outputs are token-identical on/off (regression-tested);
    # False restores the synchronous dispatch-then-consume loop (the
    # bench_serve --workload hotloop A/B baseline).
    pipelined_decode: bool = True
    # Cast model weights once at engine load (e.g. "bfloat16" — halves the
    # per-step HBM param read, the decode bottleneck; standard for serving).
    # None keeps the checkpoint dtype.
    weights_dtype: Optional[str] = None
    # Weight-only quantization at engine load ((U) vLLM quantization via the
    # HF runtime): "int8" = per-output-channel symmetric int8 on the big
    # matmuls, dequantized in the matmul operand read (ops/quantization.py)
    # — halves the decode-step HBM param read again vs bf16 and halves
    # param residency (the v5e density lever). None = off.
    quantize: Optional[str] = None
    # KV cache storage dtype of the page pool: "int8" stores K/V int8
    # with per-token-per-head dynamic scales — doubles the pool's resident
    # tokens at the same HBM. Composes with both paged-attention impls
    # (the direct-page-read kernel dequantizes in VMEM), with
    # disaggregated roles (scale blobs ride the v2 wire format), and with
    # the host tier (demote/promote batches carry scale rows). None = the
    # model activation dtype.
    kv_cache_dtype: Optional[str] = None
    # MoE expert path per phase. A request's capacity drops can never
    # depend on co-batched neighbors: a chunk program that carries several
    # prompts' chunks takes the capacity and the claiming order PER ROW
    # (layers._moe_dispatch, capacity_per_row), so the training dispatch
    # path is batch-independent there too. "auto" uses it for MoE models
    # ("dense" forces the every-expert oracle). Measured (bench_serve --workload moe, mixtral-0.8b p1024/
    # gen32/c16, one-session A/B): dispatch prefill 7.0 vs dense 6.5 req/s
    # and p50 TTFT 907 vs 1068 ms (isolated block: 10-14x at T=512-2048 —
    # the engine-level win is smaller because queueing+decode share TTFT).
    # Decode co-batches slots, so its only batch-independent dispatch is
    # the zero-drop variant (capacity = k·batch — nothing can drop); A/Bs
    # measured it a tie with dense across three sessions including a
    # decode-heavy p128/gen128 run (3.98 vs 3.96 req/s), so "auto" keeps
    # the simpler dense path; "zero_drop" selects the variant for
    # remeasurement at other batch sizes. It governs the decode-ONLY
    # programs: where a chunk program carries the decode step (PR 49) the
    # step's rows go the prefill path's drop-free way (a capacity group of
    # their own that holds every token; sorted experts have no capacity).
    moe_prefill_impl: str = "auto"   # auto|dispatch|dense
    moe_decode_impl: str = "auto"    # auto|zero_drop|dense
    # Speculative decoding (draft + batched verify): greedy requests emit
    # multiple verified tokens per decode dispatch at token-identical
    # output. Flows to the engine verbatim; the ISVC controller ships it to
    # predictor replicas inside the batching config like every other knob.
    speculative: SpeculativeSpec = Field(default_factory=SpeculativeSpec)
    # Bounded admission (load shedding): submit() rejects with
    # EngineOverloaded once this many requests wait in the scheduler queue
    # (mapped to HTTP 429 + Retry-After by the model server). 0 = unbounded
    # — the pre-hardening behavior, where overload turns into unbounded
    # queue delay and every client times out instead of a few failing fast.
    max_queue: int = 0
    # Queue-delay budget (seconds): a request still waiting for a slot this
    # long after arrival is shed with finish_reason="shed" rather than
    # admitted — by then its client has almost certainly timed out, and
    # prefilling it would only steal capacity from requests that can still
    # meet their deadlines. None = off.
    queue_delay_budget: Optional[float] = None
    # Multi-tenant QoS: per-class admission quotas/queue-delay budgets,
    # strict-priority dequeue, shed-lowest-first under overload, and
    # cross-class preemption. The defaults keep single-class
    # traffic byte-for-byte on the pre-QoS behavior (everything is
    # "standard" unless a request declares otherwise).
    qos: QoSSpec = Field(default_factory=QoSSpec)
    # Multi-tenant LoRA adapters over shared base weights (serve/lora.py):
    # requests carrying a registered model id decode through their
    # adapter's packed low-rank slices in the SAME batched dispatch as
    # base traffic. max_adapters=0 (default) = off.
    lora: LoRASpec = Field(default_factory=LoRASpec)

    @field_validator("paged")
    @classmethod
    def _only_paged(cls, value: bool) -> bool:
        if not value:
            raise ValueError(
                "paged=False: the contiguous slot cache is gone; the page "
                "pool is the engine's only KV cache (drop the key or set "
                "paged=true)")
        return value

    @model_validator(mode="after")
    def _check_role(self) -> "BatchingSpec":
        if self.role not in ENGINE_ROLES:
            raise ValueError(
                f"unknown engine role {self.role!r}; one of {ENGINE_ROLES}")
        if self.prefix_index not in ("radix", "flat"):
            raise ValueError(
                f"unknown prefix_index {self.prefix_index!r}; "
                "one of radix|flat")
        if self.host_kv_pages and self.prefix_index != "radix":
            raise ValueError(
                "host_kv_pages requires prefix_index='radix' (the "
                "flat hash has no tier lifecycle)")
        if self.remote_kv_root and not self.host_kv_pages:
            # The remote tier spills FROM the host tier (device pages
            # demote host-first; the store never sees raw device reads).
            raise ValueError(
                "remote_kv_root requires host_kv_pages > 0 (the third "
                "tier spills from the host tier, not the device)")
        if self.lora.max_adapters:
            if self.role != "unified":
                # Handoff payloads carry KV only — the adopting engine
                # would need the SAME adapter hot to continue decoding,
                # a placement contract the fleet router doesn't speak
                # yet. Multi-adapter engines serve whole requests.
                raise ValueError(
                    "lora.max_adapters requires role='unified' "
                    "(adapter KV cannot ride a handoff)")
            if self.speculative.mode != "off":
                raise ValueError(
                    "lora.max_adapters requires speculative.mode='off' "
                    "(the verify dispatch has no adapter lane yet)")
        return self


class SLOPolicy(BaseModel):
    """Signal-driven autoscaling targets ((U) Knative KPA, but the signal
    is the ENGINE's own latency histograms rather than opaque concurrency):
    the ISVC autoscaler scrapes each replica's queue-delay p95 and TTFT p95
    off /metrics, forms a utilization ratio against these targets, and
    resizes within ``min_replicas..max_replicas`` with hysteresis and a
    cooldown. Missing or stale signals HOLD the current count — an
    autoscaler must never flap on blindness."""

    model_config = ConfigDict(extra="forbid")

    # Latency targets (milliseconds). At least one must be set; when both
    # are, the binding (worse) ratio drives scaling.
    target_ttft_ms: Optional[float] = None
    target_queue_delay_ms: Optional[float] = None
    # Per-class weights for the pooled ratio when replicas expose
    # per-class p95s: interactive SLO misses count fully, batch barely —
    # batch backlog alone must not buy replicas an interactive tenant
    # doesn't need. Classes absent here default to weight 0.
    class_weights: dict[str, float] = Field(default_factory=lambda: {
        "interactive": 1.0, "standard": 0.5, "batch": 0.1})
    # Hysteresis dead band: scale up when the pooled ratio exceeds
    # ``scale_up_ratio``, down when it falls below ``scale_down_ratio``;
    # inside the band the count holds. up > down keeps the two decisions
    # from chasing each other.
    scale_up_ratio: float = 1.1
    scale_down_ratio: float = 0.5
    # Minimum quiet time between ANY two resize decisions (seconds).
    cooldown_s: float = 10.0

    @model_validator(mode="after")
    def _check(self) -> "SLOPolicy":
        if self.target_ttft_ms is None and self.target_queue_delay_ms is None:
            raise ValueError(
                "SLOPolicy needs target_ttft_ms and/or target_queue_delay_ms")
        for f in ("target_ttft_ms", "target_queue_delay_ms"):
            v = getattr(self, f)
            if v is not None and v <= 0:
                raise ValueError(f"{f} must be positive")
        if not (0 < self.scale_down_ratio < self.scale_up_ratio):
            raise ValueError("need 0 < scale_down_ratio < scale_up_ratio")
        unknown = set(self.class_weights) - set(QOS_CLASSES)
        if unknown:
            raise ValueError(
                f"unknown QoS classes in class_weights {sorted(unknown)}")
        if any(w < 0 for w in self.class_weights.values()):
            raise ValueError("class_weights must be >= 0")
        if self.cooldown_s < 0:
            raise ValueError("cooldown_s must be >= 0")
        return self


class PoolSplitSpec(BaseModel):
    """Disaggregated predictor pools: ``prefill`` prefill-specialized and
    ``decode`` decode-specialized replicas behind one token-aware router
    (engine roles ride to each replica in its batching config). The
    counts are per-pool MINIMUMS; with an ``SLOPolicy`` the autoscaler
    resizes each pool on its own signal — prefill on queue-delay p95
    (admission backlog lives there), decode on TTFT p95 of adopted
    requests (the decode-side scheduling latency) — up to the per-pool
    maximums."""

    model_config = ConfigDict(extra="forbid")

    prefill: int = 1
    decode: int = 1
    max_prefill: Optional[int] = None    # default: the minimum (fixed pool)
    max_decode: Optional[int] = None

    @model_validator(mode="after")
    def _check(self) -> "PoolSplitSpec":
        if self.prefill < 1 or self.decode < 1:
            raise ValueError("pool split needs prefill >= 1 and decode >= 1")
        if self.max_prefill is not None and self.max_prefill < self.prefill:
            raise ValueError("max_prefill < prefill")
        if self.max_decode is not None and self.max_decode < self.decode:
            raise ValueError("max_decode < decode")
        return self

    def cap(self, role: str) -> int:
        if role == "prefill":
            return self.max_prefill or self.prefill
        return self.max_decode or self.decode


class PredictorSpec(BaseModel):
    model_config = ConfigDict(extra="forbid")

    model: ModelSpec
    min_replicas: int = 1
    max_replicas: int = 1
    scale_target: int = 4            # target in-flight requests per replica (≈ KPA concurrency)
    scale_metric: str = "concurrency"
    # Signal-driven autoscaling: when set, replica count is driven by the
    # engine's own queue-delay/TTFT p95s against these targets instead of
    # the concurrency heuristic above (which remains the default).
    slo: Optional[SLOPolicy] = None
    canary_traffic_percent: Optional[int] = None
    # Disaggregated prefill/decode pools ({prefill: N, decode: M}): the
    # controller runs two role-specialized replica pools behind the
    # token-aware router instead of one homogeneous rotation. Mutually
    # exclusive with canary splits (pools ARE the traffic topology).
    pools: Optional[PoolSplitSpec] = None
    resources: TPUResourceSpec = Field(default_factory=TPUResourceSpec)
    parallelism: ParallelismSpec = Field(default_factory=ParallelismSpec)
    batching: BatchingSpec = Field(default_factory=BatchingSpec)
    # Graceful drain on scale-down/rollout (≈ pod terminationGracePeriod):
    # a retired replica stops receiving router traffic immediately, then
    # gets this long to finish in-flight requests before deletion.
    drain_deadline_s: float = 30.0

    @model_validator(mode="after")
    def _check(self) -> "PredictorSpec":
        if self.min_replicas < 0 or self.max_replicas < max(self.min_replicas, 1):
            raise ValueError("invalid replica bounds")
        if self.canary_traffic_percent is not None and not (
            0 <= self.canary_traffic_percent <= 100
        ):
            raise ValueError("canary_traffic_percent must be in [0,100]")
        # Serving scale-out: replicas handle request parallelism; the mesh
        # handles models bigger than one chip (tensor parallel). Other axes
        # (pipeline/fsdp/...) have no serving dispatch path.
        p = self.parallelism
        if p.total > 1 and p.total != p.model:
            raise ValueError(
                "serving parallelism supports the model (tensor-parallel) "
                f"axis only; got {p.axis_sizes()}")
        # Mirror JAXJobSpec's invariant: an explicit chip request must match
        # the mesh (a mismatch would crash-loop the worker at build_mesh
        # instead of failing here, at spec time).
        if p.total > 1 and self.resources.tpu_chips not in (1, p.total):
            raise ValueError(
                f"resources.tpu_chips={self.resources.tpu_chips} does not "
                f"match parallelism product {p.total} (set it to "
                f"{p.total}, or leave it 1 to derive it)")
        if self.pools is not None:
            if self.canary_traffic_percent is not None:
                raise ValueError(
                    "pools and canary_traffic_percent are mutually "
                    "exclusive (a pool split IS the traffic topology)")
            if self.batching.role != "unified":
                raise ValueError(
                    "leave batching.role='unified' with pools set — the "
                    "controller stamps each pool's role onto its replicas")
        return self


class TransformerSpec(BaseModel):
    """Pre/post-processing hop (≈ kserve transformer): a registered callable."""

    model_config = ConfigDict(extra="forbid")

    handler: str                     # registered name or "module:function"
    config: dict[str, Any] = Field(default_factory=dict)


class ExplainerSpec(BaseModel):
    """Explanation hop (≈ kserve explainer — the third component of the
    triad): a registered token-attribution handler served on the
    ``:explain`` route. Built-ins: "grad_x_input" (saliency via a VJP
    through the decoder) and "leave_one_out" (batched occlusion); custom
    handlers register like transformers (serve/explain.py)."""

    model_config = ConfigDict(extra="forbid")

    handler: str = "grad_x_input"    # registered name or "module:function"
    config: dict[str, Any] = Field(default_factory=dict)


class InferenceServiceSpec(BaseModel):
    model_config = ConfigDict(extra="forbid")

    predictor: PredictorSpec
    transformer: Optional[TransformerSpec] = None
    explainer: Optional[ExplainerSpec] = None


class InferenceServiceStatus(ConditionMixin):
    model_config = ConfigDict(extra="forbid")

    url: Optional[str] = None
    ready_replicas: int = 0
    # None = the autoscaler hasn't decided yet (first reconcile seeds it);
    # 0 is a real state — scaled to zero (min_replicas=0, idle).
    desired_replicas: Optional[int] = None
    # Disaggregated pool sizes (role -> desired count), autoscaler-owned
    # once seeded; empty on non-pooled services.
    desired_pool_replicas: dict[str, int] = Field(default_factory=dict)
    traffic: dict[str, int] = Field(default_factory=dict)  # generation -> percent
    latest_ready_generation: Optional[int] = None


@register_kind
class InferenceService(ApiObject):
    KIND = "InferenceService"
    API_VERSION = "serving.tpu.kubeflow.dev/v1"

    spec: InferenceServiceSpec
    status: InferenceServiceStatus = Field(default_factory=InferenceServiceStatus)


class ServingRuntimeSpec(BaseModel):
    """Maps a model format to an engine implementation + defaults
    (≈ ServingRuntime/ClusterServingRuntime CRDs)."""

    model_config = ConfigDict(extra="forbid", protected_namespaces=())

    supported_formats: list[ModelFormat]
    engine: str                      # registered engine factory name
    defaults: dict[str, Any] = Field(default_factory=dict)


@register_kind
class ServingRuntime(ApiObject):
    KIND = "ServingRuntime"
    API_VERSION = "serving.tpu.kubeflow.dev/v1"

    spec: ServingRuntimeSpec
