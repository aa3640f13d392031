"""Shared constants: env-var names, file names, well-known job types.

Mirrors the role of ``com.linkedin.tony.Constants`` (tony-core, upstream path
``tony-core/src/main/java/com/linkedin/tony/Constants.java``, unverified — see
SURVEY.md §0): the single place where the env-var contract between the AM, the
task executors, and user code is written down.
"""

# --- Environment contract: AM -> TaskExecutor -------------------------------
# (reference: Constants.JOB_NAME / TASK_INDEX / AM_HOST / AM_PORT etc., set in
#  TonyApplicationMaster#buildContainerLaunchContext)
ENV_JOB_NAME = "TONY_JOB_NAME"              # jobtype, e.g. "worker", "ps", "chief"
ENV_TASK_INDEX = "TONY_TASK_INDEX"          # integer index within the jobtype
ENV_TASK_NUM = "TONY_NUM_TASKS"             # total number of tasks in the job
ENV_AM_ADDRESS = "TONY_AM_ADDRESS"          # host:port of the AM ApplicationRpc
ENV_APP_ID = "TONY_APP_ID"                  # application id, e.g. "app_1700000000_0001"
ENV_ATTEMPT_ID = "TONY_ATTEMPT_ID"          # AM attempt ordinal (gang restart)
ENV_CONF_PATH = "TONY_CONF_PATH"            # path to the serialized job config
ENV_CONTAINER_ID = "TONY_CONTAINER_ID"      # container id for this executor
ENV_LOG_DIR = "TONY_LOG_DIR"                # directory for executor+user logs
ENV_SRC_DIR = "TONY_SRC_DIR"                # localized user source directory
ENV_VENV = "TONY_VENV"                      # localized virtualenv (optional)
ENV_RESOURCES_DIR = "TONY_RESOURCES_DIR"    # staged tony.containers.resources
ENV_SUBMIT_TS = "TONY_SUBMIT_TS"            # client submit wall-clock (epoch s)

# --- Environment contract: TaskExecutor -> user process ---------------------
# (reference: MLGenericRuntime common env + per-runtime additions)
ENV_JOB_TYPE = "JOB_NAME"                   # TonY exports JOB_NAME/TASK_INDEX too
ENV_TASK_INDEX_USER = "TASK_INDEX"
ENV_DIST_SPEC = "CLUSTER_SPEC"              # JSON {jobtype: ["host:port", ...]}
ENV_TB_PORT = "TB_PORT"                     # reserved TensorBoard port (chief/tb)

# JAXRuntime rendezvous (the north-star JAX path; consumed by
# tony_tpu.distributed.initialize() and by jax.distributed directly)
ENV_COORDINATOR_ADDRESS = "TONY_COORDINATOR_ADDRESS"
ENV_PROCESS_ID = "TONY_PROCESS_ID"
ENV_NUM_PROCESSES = "TONY_NUM_PROCESSES"
ENV_LOCAL_DEVICE_IDS = "TONY_LOCAL_DEVICE_IDS"
ENV_PROFILER_PORT = "TONY_PROFILER_PORT"    # jax.profiler server (§5.1 hook)
# Checkpoint plane (tony_tpu.ckpt): JAXRuntime exports these from
# tony.ckpt.dir/every/keep; train.train_loop reads them as its defaults,
# and the executor scans the same dir to report the last COMMITTED step
# over the heartbeat RPC.
ENV_CKPT_DIR = "TONY_CKPT_DIR"
ENV_CKPT_EVERY = "TONY_CKPT_EVERY"
ENV_CKPT_KEEP = "TONY_CKPT_KEEP"
# Input-data plane (tony_tpu.data): JAXRuntime exports tony.data.seed so
# the whole gang derives the SAME deterministic example stream without the
# script threading a seed through (Dataset's default seed). The shard
# itself needs no new env — ShardSpec.from_env reads the rendezvous pair
# (TONY_PROCESS_ID/TONY_NUM_PROCESSES) with the generic executor pair
# (TONY_TASK_INDEX/TONY_NUM_TASKS) as fallback.
ENV_DATA_SEED = "TONY_DATA_SEED"
# Serving plane (tony_tpu.serve): the executor exports a per-container
# stats-file path; the replica's engine publishes qps/p99/queue-depth
# there and the executor's heartbeat loop piggybacks it to the AM (both
# sides jax-free), where the replica autoscaler reads it.
ENV_SERVE_STATS = "TONY_SERVE_STATS"
# The task's start timeline (tony_tpu.profiler): the executor stamps the
# moment it launches the user process (epoch seconds); the first process
# to import the profiler takes the stamp as its timeline's origin
# (``t_launch``, the start of ``tony:python_start``) and out of the
# environment. Plumbing between executor and task, not a switch.
ENV_LAUNCH_TIME = "TONY_LAUNCH_TIME"
# Elastic resize (tony_tpu.am.resize): the executor exports a drain-file
# path; when the AM's heartbeat response carries the drain directive the
# executor creates the file, and train_loop — polling it between steps —
# commits model+data-cursor and exits EXIT_DRAINED.
ENV_DRAIN_FILE = "TONY_DRAIN_FILE"
# Continuous weight publication (tony_tpu.publish): JAXRuntime exports
# tony.publish.every; train_loop advances the ckpt root's published.json
# pointer every N committed periodic saves, and the executor's heartbeat
# loop reads the pointer (jax-free) and announces it to the AM.
ENV_PUBLISH_EVERY = "TONY_PUBLISH_EVERY"
# Shared per-gang train AOT cache dir (tony_tpu.ckpt.aot): exported from
# tony.train.aot-cache; make_accum_train_step deserializes a gang mate's
# compiled step instead of re-tracing (first writer wins on populate).
ENV_TRAIN_AOT_CACHE = "TONY_TRAIN_AOT_CACHE"

# TFRuntime / PyTorchRuntime / HorovodRuntime / MXNetRuntime rendezvous vars
ENV_TF_CONFIG = "TF_CONFIG"
ENV_MASTER_ADDR = "MASTER_ADDR"
ENV_MASTER_PORT = "MASTER_PORT"
ENV_RANK = "RANK"
ENV_WORLD_SIZE = "WORLD_SIZE"
ENV_LOCAL_RANK = "LOCAL_RANK"
ENV_INIT_METHOD = "INIT_METHOD"
ENV_HOROVOD_CONTROLLER = "HOROVOD_CONTROLLER"
ENV_HOROVOD_RENDEZVOUS_ADDR = "HOROVOD_GLOO_RENDEZVOUS_ADDR"
ENV_HOROVOD_RENDEZVOUS_PORT = "HOROVOD_GLOO_RENDEZVOUS_PORT"
ENV_HOROVOD_RANK = "HOROVOD_RANK"
ENV_HOROVOD_SIZE = "HOROVOD_SIZE"
ENV_HOROVOD_LOCAL_RANK = "HOROVOD_LOCAL_RANK"
ENV_HOROVOD_LOCAL_SIZE = "HOROVOD_LOCAL_SIZE"
ENV_HOROVOD_CROSS_RANK = "HOROVOD_CROSS_RANK"
ENV_HOROVOD_CROSS_SIZE = "HOROVOD_CROSS_SIZE"
ENV_DMLC_PS_ROOT_URI = "DMLC_PS_ROOT_URI"
ENV_DMLC_PS_ROOT_PORT = "DMLC_PS_ROOT_PORT"
ENV_DMLC_ROLE = "DMLC_ROLE"
ENV_DMLC_NUM_SERVER = "DMLC_NUM_SERVER"
ENV_DMLC_NUM_WORKER = "DMLC_NUM_WORKER"

# TPU topology env injected by JAXRuntime on real pods (libtpu contract)
ENV_TPU_WORKER_ID = "TPU_WORKER_ID"
ENV_TPU_WORKER_HOSTNAMES = "TPU_WORKER_HOSTNAMES"
ENV_TPU_VISIBLE_CHIPS = "TPU_VISIBLE_CHIPS"
ENV_TPU_CHIPS_PER_HOST_BOUNDS = "TPU_CHIPS_PER_HOST_BOUNDS"
# Host-subdivision contract (several tasks sharing one host's chips):
ENV_TPU_PROCESS_BOUNDS = "TPU_PROCESS_BOUNDS"
ENV_TPU_CHIPS_PER_PROCESS_BOUNDS = "TPU_CHIPS_PER_PROCESS_BOUNDS"
ENV_TPU_PROCESS_ADDRESSES = "TPU_PROCESS_ADDRESSES"
ENV_TPU_PROCESS_PORT = "TPU_PROCESS_PORT"
ENV_CLOUD_TPU_TASK_ID = "CLOUD_TPU_TASK_ID"
# Multi-slice (megascale) DCN coordination: exported when tony.jax.slices>1
# so libtpu bridges the slices over DCN and the hierarchical gradient
# reduce (tony_tpu.parallel.overlap) has a cross-slice axis to ride.
ENV_MEGASCALE_COORDINATOR_ADDRESS = "MEGASCALE_COORDINATOR_ADDRESS"
ENV_MEGASCALE_NUM_SLICES = "MEGASCALE_NUM_SLICES"
ENV_MEGASCALE_SLICE_ID = "MEGASCALE_SLICE_ID"
ENV_MEGASCALE_PORT = "MEGASCALE_PORT"
# TPU compiler knobs (JAXRuntime injects the comm/compute-overlap set —
# latency-hiding scheduler + async collectives — unless disabled by conf).
# They travel in libtpu's own variable: jaxlib parses XLA_FLAGS itself on
# every compile (``CompileOptions()``) and aborts the process on the
# xla_tpu_* names, which only libtpu's flag registry knows.
ENV_LIBTPU_INIT_ARGS = "LIBTPU_INIT_ARGS"
# Platform pin for a task that was granted chips (tony.<jobtype>.tpus > 0):
# unset, jax falls back to the CPU when the TPU fails to initialise and
# the job "succeeds" there; pinned, the task dies instead.
ENV_JAX_PLATFORMS = "JAX_PLATFORMS"
# jax's own option: a regex removed from every source-file name it writes
# into a program's locations. The executor sets it to its container's
# sandbox prefix (``TaskExecutor.source_prefix_regex``) unless the user did:
# a Pallas kernel's Mosaic module keeps those names, they survive jax's
# strip of debug info, and so they are part of the compile cache's key.
ENV_JAX_SOURCE_FILE_REGEX = "JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX"

# --- Well-known job types ---------------------------------------------------
# (reference: open-ended; these are the conventional names used by the success
#  policy in TonyApplicationMaster / TonySession)
CHIEF = "chief"
MASTER = "master"
PS = "ps"
WORKER = "worker"
EVALUATOR = "evaluator"
TENSORBOARD = "tensorboard"
NOTEBOOK = "notebook"
DRIVER = "driver"               # Horovod-style driver task
SCHEDULER = "scheduler"         # MXNet kvstore scheduler
SERVE = "serve"                 # online-serving replica (tony_tpu.serve)

# Job types whose completion drives the "chief done => job done" policy.
CHIEF_LIKE_JOB_TYPES = (CHIEF, MASTER)

# Sidecar job types: never part of the ML rendezvous world (excluded from
# RANK/WORLD_SIZE/coordinator selection the way the reference's TFRuntime
# excludes them from TF_CONFIG). Distinct from *untracked* types: ``ps`` is
# untracked by default but IS a cluster member.
SIDECAR_JOB_TYPES = (TENSORBOARD, NOTEBOOK, DRIVER)

# --- File-layout conventions ------------------------------------------------
TONY_XML = "tony.xml"                       # user config file name (compat)
TONY_JOB_JSON = "tony-job.json"             # serialized effective config
JHIST_SUFFIX = ".jhist"                     # history file (JSONL here, Avro in ref)
JHIST_INPROGRESS_SUFFIX = ".jhist.inprogress"
EVENTS_DIR_INTERMEDIATE = "intermediate"    # AM writes here while running
EVENTS_DIR_FINISHED = "finished"            # moved here on completion
EXECUTOR_LOG_NAME = "executor.log"
USER_STDOUT_NAME = "stdout.log"
USER_STDERR_NAME = "stderr.log"

# --- Exit codes (reference: TaskExecutor / TonyClient contract) -------------
EXIT_SUCCESS = 0
EXIT_FAILURE = 1
EXIT_AM_ERROR = 10          # AM internal error
EXIT_LOST_TASK = 11         # task lost to missed heartbeats
EXIT_PREEMPTED = 12         # container preempted by the scheduler
EXIT_KILLED = 13            # killed by client / untracked-task teardown
EXIT_DRAINED = 14           # clean drain exit (elastic resize commit)
