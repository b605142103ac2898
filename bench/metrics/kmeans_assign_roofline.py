"""Share of its roofline that the ``kmeans_assign`` kernel reaches in the
traced window. Each dedup of ``n`` active tiles into ``k = max(2, n //
2)`` clusters needs one assignment against the first centre, one per
further k-means++ pick (``k - 1``, against the newest centre), one per
Lloyd iteration and a final one against all ``k`` centres. A call
against ``c`` centres of ``d = 9`` features reads the features and
centres and writes an index and a distance per row: ``4 (n d + c d) + 8
n`` bytes and ``2 n c d + 3 n c`` operations. The least time of a call
is the larger of bytes over HBM bandwidth and operations over the bf16
peak; the share is their sum over the kernel's device time."""

# the kernel is the custom call inside the dedup programs
PATTERN = r"custom.call"
PROGRAMS = ("_dedup_core_body", "_dedup_finalize_body", "_dedup_multi_core",
            "_dedup_finalize_multi")
D = 9


def call_cost(n: int, c: int, d: int = D):
    return 4.0 * (n * d + c * d) + 8.0 * n, 2.0 * n * c * d + 3.0 * n * c


def needed_seconds(n: int, iters: int, peaks) -> float:
    k = max(2, n // 2)
    calls = [1] * k + [k] * (iters + 1)
    return sum(max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops"])
               for b, f in (call_cost(n, c) for c in calls))


def read(run):
    secs, _ = run["trace"].ops_matching(PATTERN, *PROGRAMS)
    rows = run["tally"].get("dedup_rows", [])
    if secs <= 0 or not rows:
        return None
    iters = run["traffic"]["dedup"]["iters"]
    least = sum(needed_seconds(n, iters, run["peaks"]) for n in rows)
    return 100.0 * least / secs
