"""The busiest held expert's tokens over the mean held expert's, over the
window's steps and the expert layers (``DroplessMoE``'s counters, at the
taps' cadence)."""


def read(obs):
    counters = obs.get("expert_counters") or {}
    held, most = counters.get("assignments_held"), counters.get("expert_max")
    if not held or not most:
        return None
    n_held = len(obs["config"]["experts_held"])
    ratios = [m * n_held / a for row_a, row_m in zip(held, most)
              for a, m in zip(row_a, row_m) if a > 0]
    return sum(ratios) / len(ratios) if ratios else None
