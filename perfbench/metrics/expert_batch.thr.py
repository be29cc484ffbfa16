"""MoE: tokens per held expert per MoE layer per decode tick, over the
window: the engine's ``expert_tokens`` counter (token->expert assignments
that landed on the experts this chip holds, summed over MoE layers) over
ticks x ``held_experts`` (MoE layers x experts held).  Under the
deployment's expert parallelism each expert's matmuls take this many rows.
None where the program has no such counter."""


def read(m):
    if "expert_tokens" not in m or not m.get("ticks") \
            or not m.get("held_experts"):
        return None
    return m["expert_tokens"] / (m["ticks"] * m["held_experts"])
