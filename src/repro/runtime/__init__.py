"""The asynchronous runtime (paper §3), structured in three layers:

  * **Service** (``service.py``) — the uniform start/stop/join lifecycle,
    health state, and per-service ``MetricsRegistry`` that every component
    (rollout workers, the Inference-as-a-Service pool with dynamic-window
    batching (eq. 1), trainer loops, imagination producers, WM trainers)
    implements, wired on a ``ServiceRegistry`` bus;
  * **ExperienceChannel** (``experience.py``) — the data plane: FIFO /
    ring channels with pluggable backpressure and the
    ``MixedExperienceSource`` composing real and imagined segments;
  * **Scheduler** (``scheduler.py``) — ``FreeRunScheduler`` (the fully
    asynchronous pipeline) and ``BarrierScheduler`` (the synchronous
    baseline with step/episode/cluster barriers of Figure 1) pacing the
    SAME services.

``orchestrator.AcceRLSystem`` composes the layers; extensions (the world
model, paper §4) plug in via ``system.attach(...)``. The versioned weight
store implements the drain protocol (App. D.6)."""
from repro.runtime.weight_store import (  # noqa: F401
    DirectTransport,
    DiskTransport,
    SerializedTransport,
    VersionedWeightStore,
)
from repro.runtime.service import (  # noqa: F401
    MetricsRegistry,
    NullGate,
    RolloutGate,
    Service,
    ServiceRegistry,
    ServiceState,
)
from repro.runtime.experience import (  # noqa: F401
    ExperienceChannel,
    FifoChannel,
    MixedExperienceSource,
    RingChannel,
)
from repro.runtime.scheduler import (  # noqa: F401
    BarrierGate,
    BarrierScheduler,
    FreeRunScheduler,
    Scheduler,
    ServiceFailure,
)
from repro.runtime.inference import InferenceService  # noqa: F401
from repro.runtime.pipeline_exec import (  # noqa: F401
    Instruction,
    PipelineExecutor,
    PipelineOp,
    Submesh,
    SubmeshLayout,
    build_train_schedules,
    validate_schedules,
)
from repro.runtime.rollout import RolloutWorker  # noqa: F401
from repro.runtime.step_program import (  # noqa: F401
    StageSpec,
    StepProgram,
    build_train_step_program,
)
from repro.runtime.trainer import TrainerWorker  # noqa: F401
from repro.runtime.transport import (  # noqa: F401
    ChannelClosed,
    RemoteWorkerSpec,
    RestartPolicy,
    ShmChannel,
    SocketChannel,
    SupervisedWorker,
    Supervisor,
    TransportError,
    TransportServer,
    WeightStoreTransport,
)
from repro.runtime.orchestrator import AcceRLSystem  # noqa: F401
