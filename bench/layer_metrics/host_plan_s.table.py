"""Seconds of host-side capacity planning (``plan_dist_join_sizes``) in
set-up, on the host clock."""


def read(rec):
    return rec.get("host_plan_s")
