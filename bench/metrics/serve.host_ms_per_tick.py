"""Host time per engine tick outside the decode call: the harness's span
around ``ServeEngine.step`` minus the engine's own ``serve.decode_s``
(which ends in the logits' pull to the host), per tick."""


def read(run):
    s = run.stats
    if not s.get("ticks") or "decode_s" not in s:
        return None
    return (s["host_step_s"] - s["decode_s"]) / s["ticks"] * 1e3
