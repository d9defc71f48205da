"""Mean rows per held expert per MoE layer in a decode step over the
window: the scheduler's ``serve_moe_held_assignments`` counter over its
``serve_moe_experts_hit`` observations (one per MoE layer and decode
step) and the experts held. In the deployment it stands for (four chips
of 64 lanes), each expert sees about 24 tokens a step."""


def read(data):
    reg = getattr(data, "registry", None)
    if not reg or reg["layer_steps"] <= 0:
        return None
    return reg["held_assignments"] / (reg["layer_steps"]
                                      * reg["experts_held"])
