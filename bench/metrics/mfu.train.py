"""mfu.train: the operations of every training step completed in the
traced window (forward and backward as ``counts.train_step_flops``
counts them, recomputation not counted) over the window times the chips
times each chip's peak."""


def read(trace, facts):
    if facts.get("kind") != "train" or not facts["steps"]:
        return None
    return 100.0 * facts["step_flops"] * facts["steps"] / (
        trace.mean_window_s() * facts["chips"] * facts["peak"]["flops_per_s"])
