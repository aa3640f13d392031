"""Shared adapter base (reference: ``runtime/MLGenericRuntime.java``).

Provides the common env every runtime exports — job name, task index, the full
cluster spec, app metadata — plus per-jobtype extra env from
``tony.<jobtype>.env``.
"""

from __future__ import annotations

import json
from typing import Dict

from tony_tpu import conf as conf_mod
from tony_tpu import constants
from tony_tpu.runtime import TaskContext, TaskExecutorAdapter


class MLGenericTaskAdapter(TaskExecutorAdapter):
    """Common env builder; framework adapters extend :meth:`framework_env`."""

    def need_reserve_profiler_port(self, ctx: TaskContext) -> bool:
        """Whatever the framework (a training worker is "jax", a `tony
        serve` replica "standalone"): a job that set
        ``tony.task.profiler.enabled`` gets a port for every task that is
        not a sidecar."""
        return (not ctx.is_sidecar()
                and ctx.conf.get_bool("tony.task.profiler.enabled", False))

    def build_task_env(self, ctx: TaskContext) -> Dict[str, str]:
        env: Dict[str, str] = {
            constants.ENV_JOB_TYPE: ctx.job_type,
            constants.ENV_TASK_INDEX_USER: str(ctx.index),
            constants.ENV_DIST_SPEC: json.dumps(ctx.cluster_spec, sort_keys=True),
            constants.ENV_JOB_NAME: ctx.job_type,
            constants.ENV_TASK_INDEX: str(ctx.index),
            constants.ENV_TASK_NUM: str(ctx.num_tasks()),
            constants.ENV_APP_ID: ctx.app_id,
            constants.ENV_ATTEMPT_ID: str(ctx.attempt_id),
            constants.ENV_AM_ADDRESS: ctx.am_address,
        }
        if ctx.tb_port is not None:
            env[constants.ENV_TB_PORT] = str(ctx.tb_port)
        # Profiler hook (SURVEY.md §5.1): tony_tpu.distributed.initialize
        # and serve.replica.main start jax.profiler.start_server on this
        # port in the user process. The port is executor-reserved and
        # EPHEMERAL (shipped to the AM via register_callback_info) — a
        # conf-fixed base+rank collided across overlapping jobs on one
        # host, and the trace client would dial a dying predecessor's
        # server.
        if ctx.profiler_port is not None:
            env[constants.ENV_PROFILER_PORT] = str(ctx.profiler_port)
        env.update(ctx.conf.task_env(ctx.job_type))
        env.update(self.framework_env(ctx))
        if ctx.conf.get_int(conf_mod.tpus_key(ctx.job_type), 0) > 0:
            # Whatever the framework (a `tony serve` replica is
            # "standalone"): a task granted chips runs on them or dies.
            env[constants.ENV_JAX_PLATFORMS] = "tpu"
        return env

    def framework_env(self, ctx: TaskContext) -> Dict[str, str]:
        return {}
