"""Device: the operations the window's work requires (its prompts'
prefills and every decoded event, ``harness.work``) over what the chips'
bf16 peak could do in the window, in %."""


def read(m):
    if not m.get("flops"):
        return None
    return 100.0 * m["flops"] / (m["window_s"] * m["chips"]
                                 * m["peaks"]["bf16_flops_per_s"])
