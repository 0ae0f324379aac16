"""Engine step telemetry.

The engine's device loop builds one :class:`StepRecord` per scheduler
iteration and hands the same object to :meth:`StepTelemetry.observe`, to
``UtilizationTracker.observe`` and to the flight ring (plain Python
assignments under the GIL — safe to read from the asyncio thread).  The
snapshot rides the existing telemetry path: ``JaxLlmEngine.stats()`` merges
it, ``WorkerMetricsPublisher`` ships it as ``ForwardPassMetrics``, and
``components/metrics_service.py`` exports it as ``dyn_worker_*`` Prometheus
gauges — no new registry, one coherent pipeline.

Every iteration is booked by KIND: ``prompt`` when the window it is booked
to carried at least one prompt token, ``decode`` when it carried none.  The
window an iteration is booked to is the one the DEVICE was executing while
the iteration ran: under the overlapped pipeline iteration k schedules and
dispatches window k and then waits for window k-1, so its time belongs to
window k-1 (the engine passes that window's kind; see
``JaxLlmEngine._device_loop``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

KIND_DECODE, KIND_PROMPT = "decode", "prompt"


@dataclass(slots=True)
class StepRecord:
    """Everything one engine iteration knows about itself, built once."""

    iteration: int = 0
    kind: str = KIND_DECODE             # of the window this time is booked to
    duration_s: float = 0.0
    readback_wait_s: float = 0.0        # host blocked on the device
    num_running: int = 0
    num_waiting: int = 0
    kv_active_blocks: int = 0
    kv_total_blocks: int = 0
    prefill_tokens: int = 0             # prompt tokens computed this step
    decode_tokens: int = 0              # decode positions computed this step
    decode_lane_steps: int = 0          # running decode lanes x device steps
    attn_ctx_tokens: int = 0            # attended context positions
    weight_streams: float = 0.0         # full weight passes dispatched
    emitted_tokens: int = 0
    # no lane of the window this time is booked to samples, so its program
    # took ``sample_tokens``' empty branch: no sort over the vocabulary
    sample_sort_skipped: bool = False
    # filled by UtilizationTracker.observe (the cost model lives there)
    flops: float = 0.0


@dataclass
class StepSnapshot:
    """State of the most recent engine step."""

    iteration: int = 0
    num_running: int = 0
    num_waiting: int = 0
    batch_occupancy_perc: float = 0.0   # running lanes / max_batch_size
    kv_usage_perc: float = 0.0          # used blocks / pool blocks
    kv_active_blocks: int = 0
    step_duration_s: float = 0.0
    timestamp_s: float = 0.0
    prefill_tokens: int = 0             # prompt tokens computed this step
    decode_tokens: int = 0              # decode positions computed this step


class StepTelemetry:
    """Latest-step snapshot + monotone counters, cheap enough for every step."""

    def __init__(self, max_batch_size: int):
        self.max_batch_size = max(max_batch_size, 1)
        self.snapshot = StepSnapshot()
        self.steps_total = 0
        self.busy_steps_total = 0        # steps with at least one running lane
        self.sample_sort_skipped_steps_total = 0  # those of them that sorted no vocabulary
        self.step_time_total_s = 0.0
        # by kind of the window the time is booked to
        self.kind_steps_total = {KIND_DECODE: 0, KIND_PROMPT: 0}
        self.kind_time_total_s = {KIND_DECODE: 0.0, KIND_PROMPT: 0.0}
        self.host_time_total_s = 0.0     # step time less readback wait
        self.decode_lane_steps_total = 0

    def observe(self, rec: StepRecord) -> None:
        self.snapshot = StepSnapshot(
            iteration=rec.iteration,
            num_running=rec.num_running,
            num_waiting=rec.num_waiting,
            batch_occupancy_perc=rec.num_running / self.max_batch_size,
            kv_usage_perc=(
                rec.kv_active_blocks / rec.kv_total_blocks
                if rec.kv_total_blocks else 0.0
            ),
            kv_active_blocks=rec.kv_active_blocks,
            step_duration_s=rec.duration_s,
            timestamp_s=time.time(),
            prefill_tokens=rec.prefill_tokens,
            decode_tokens=rec.decode_tokens,
        )
        self.steps_total += 1
        if rec.num_running:
            self.busy_steps_total += 1
            self.sample_sort_skipped_steps_total += rec.sample_sort_skipped
        self.step_time_total_s += rec.duration_s
        self.kind_steps_total[rec.kind] += 1
        self.kind_time_total_s[rec.kind] += rec.duration_s
        self.host_time_total_s += max(0.0, rec.duration_s - rec.readback_wait_s)
        self.decode_lane_steps_total += rec.decode_lane_steps

    def stats(self) -> dict:
        """Merged into ``JaxLlmEngine.stats()`` (names stable: the wire
        protocol and the Prometheus exporter key off them).  The ``step_*``
        names are the state AT the latest step — a coherent point-in-time
        view, unlike the live scheduler/allocator reads the engine's other
        stats fields take mid-drain."""
        s = self.snapshot
        return {
            "batch_occupancy_perc": s.batch_occupancy_perc,
            "step_num_running": s.num_running,
            "step_num_waiting": s.num_waiting,
            "step_kv_usage_perc": s.kv_usage_perc,
            "step_kv_active_blocks": s.kv_active_blocks,
            "engine_steps_total": self.steps_total,
            "engine_busy_steps_total": self.busy_steps_total,
            "sample_sort_skipped_steps_total": self.sample_sort_skipped_steps_total,
            "engine_step_time_total_s": self.step_time_total_s,
            "last_step_duration_s": s.step_duration_s,
            "engine_decode_steps_total": self.kind_steps_total[KIND_DECODE],
            "engine_decode_step_time_total_s": self.kind_time_total_s[KIND_DECODE],
            "engine_prompt_steps_total": self.kind_steps_total[KIND_PROMPT],
            "engine_prompt_step_time_total_s": self.kind_time_total_s[KIND_PROMPT],
            "engine_host_time_total_s": self.host_time_total_s,
            "decode_lane_steps_total": self.decode_lane_steps_total,
        }
