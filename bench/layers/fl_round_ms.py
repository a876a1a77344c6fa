"""FL: device time of the ops under the ``fl_round`` and ``pod_merge``
frames (selection, codec, Alg. 1, Alg. 2 fine-tuning, cloud merge), per
round."""


def read(ctx):
    tr = ctx["trace"]
    t = tr.frame_time("fl_round") + tr.frame_time("pod_merge")
    if not t or not ctx["rounds"]:
        return None
    return 1e3 * t / ctx["rounds"]
