"""How late the load generator submitted: the 95th percentile (nearest
rank) of submit time minus due time, in milliseconds."""

from pb.harness import nearest_rank


def read(ctx):
    late = ctx["layer"].get("late_s")
    if not late:
        return None
    return 1000.0 * nearest_rank(late, 95)
