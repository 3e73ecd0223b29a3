"""Collective operations the device ran per step, printed beside the
bucket plan's count (PR 21: 19 against 18 + the loss mean)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    n = tr["collective_count"] / tr["steps"]
    print(f"perfbench: collectives per step {n}, bucket plan "
          f"{ctx['result'].get('bucket_plan_buckets')}", flush=True)
    return n
