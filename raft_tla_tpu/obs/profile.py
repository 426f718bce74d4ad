"""The ``jax.profiler`` capture window behind ``--xla-profile``.

:class:`XlaProfileCapture` brackets N of the engine's real chunk
dispatches in one ``jax.profiler`` trace (``--xla-profile[=N]`` /
``XLA_PROFILE`` directive): the time of each stage inside the fused
chunk program is read from that capture, where the program names its
stages (engine/chunk.py ``STAGES``; ``benchmark/readers`` reduce it to
metrics).

jax is imported lazily, keeping ``obs`` importable in device-less
tooling like the rest of the package.
"""

from __future__ import annotations

from typing import Optional


class XlaProfileCapture:
    """Opt-in ``jax.profiler`` trace window over N sampled chunk calls —
    the hardware-truth layer (``--xla-profile[=N]`` / ``XLA_PROFILE``
    directive).

    ``jax.profiler.start_trace`` sees inside the program — which XLA
    kernels run, their launch count and their device time (XPlane
    protos + a Perfetto-openable trace under
    ``<logdir>/plugins/profile/...``).

    Correlation: every engine span is in the capture itself, on the
    host's ``python`` line, as ``raft.<name>`` with its arguments as
    stats (obs/tracing.py) — ``raft.chunk`` with ``call=<i>`` is the
    dispatch this window counts — so the device timeline and the
    ``--trace-out`` Chrome trace line up by name and call index.

    Observational and fail-soft: the capture never changes what the
    engine computes, and a profiler that cannot start (unsupported
    backend, missing permissions) records its failure in
    the ``xla_profile`` event instead of killing the run.
    """

    def __init__(self, logdir: str, chunks: int):
        self.logdir = logdir
        self.chunks = max(1, int(chunks))
        self.steps = 0
        self.active = False
        self.done = False
        self.status: Optional[str] = None

    def _start(self) -> None:
        import jax
        try:
            import os
            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
            self.active = True
            self.status = "ok"
        except Exception as e:
            self.done = True
            self.status = f"start failed: {type(e).__name__}: {e}"

    def step(self):
        """Context manager bracketing ONE chunk dispatch.  Starts the
        trace lazily on the first call (so warm-up compilation never
        pollutes the capture) and stops after ``chunks`` calls.  A no-op
        once done."""
        from contextlib import contextmanager

        @contextmanager
        def _cm():
            if self.done:
                yield
                return
            if not self.active:
                self._start()
                if self.done:           # start failed
                    yield
                    return
            self.steps += 1
            try:
                yield
            finally:
                if self.steps >= self.chunks:
                    self.stop()
        return _cm()

    def stop(self) -> None:
        if not self.active:
            return
        self.active = False
        self.done = True
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            self.status = f"stop failed: {type(e).__name__}: {e}"

    def summary(self) -> dict:
        """The ``xla_profile`` event's ``capture`` payload object."""
        return {"logdir": self.logdir, "chunks": self.chunks,
                "steps": self.steps,
                "status": self.status or "never started",
                "span_name": "chunk"}

    def finish(self, evlog) -> None:
        """Run-end hook: close an open window (early-exit runs) and emit
        the ``xla_profile`` event + flight record."""
        self.stop()
        evlog.emit("xla_profile", capture=self.summary())
        try:
            from .flight import RECORDER
            RECORDER.record("xla_profile", capture=self.summary())
        except Exception:
            pass
