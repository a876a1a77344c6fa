"""CRL episode: device time of the ops under the ``fleet_episode`` frame
(environment steps, policy, buffer insert, PPO update), per episode."""


def read(ctx):
    t = ctx["trace"].frame_time("fleet_episode")
    if not t or not ctx["episodes"]:
        return None
    return 1e3 * t / ctx["episodes"]
