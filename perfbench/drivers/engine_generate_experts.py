"""``engine_generate``'s closed loop for a mixture-of-experts model, with
the engine's ``expert_tokens`` counter read at the window's edges beside
the harness's own counters, and ``held_experts`` (MoE layers x experts
held here) for the reader of ``expert_batch.thr``.  On a program without
the counter nothing more is read.

End-to-end number: ``events_per_s``, as in ``engine_generate``.
"""
from harness import byname, window

_base = byname.load("drivers", "engine_generate")


class _Window(window.Window):
    def _snap(self, name):
        super()._snap(name)
        got = [e.health_stats().get("expert_tokens") for e in self.engines]
        if got and None not in got:
            self.snaps[name]["counters"]["expert_tokens"] = sum(got)


def run(run) -> window.Outcome:
    # the base driver builds its window by this module-level name; it is
    # put back after the run, so that the shared module stays as loaded
    _base.Window = _Window
    try:
        out = _base.run(run)
    finally:
        _base.Window = window.Window
    m = run.cfg["model"]
    out.layer["held_experts"] = ((m["n_layers"] - m["first_dense_layers"])
                                 * m["n_experts"])
    return out
