"""Work that one ``mc_eval_population`` launch needs: S perturbed
instances of each of D designs applied to one shared (M, C) batch.

Bytes: x read once (M x C), the lower and upper interval tables
(D, S, C, 2^N) each, the (C, 2^N) level ladder, the drifted range rows
(S, C) twice, and the (D, S, M, C) output, all float32. The per-element
work is a 2^N-way interval compare/select, for which no peak is
published, so the roofline of this kernel is bound by bytes alone.
"""


def work(d: int, s: int, m: int, c: int, bits: int) -> dict:
    n = 2 ** bits
    return {"flops": 0.0,
            "bytes": 4.0 * (m * c + 2 * d * s * c * n + c * n + 2 * s * c
                            + d * s * m * c)}
