"""Counts XLA backend compiles and their seconds via jax.monitoring."""


class CompileCounter:
    def __init__(self, jax):
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs
