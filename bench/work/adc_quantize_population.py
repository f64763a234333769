"""Work that one ``adc_quantize_population`` launch needs: P pruned ADC
banks applied to one shared (M, C) batch.

Bytes: x read once (M x C), the P baked (C, 2^N) value tables, the two
(1, C) range rows, and the (P, M, C) output, all float32. Operations: one
code computation and a 2^N-way compare/select per output element. No
peak is published for the vector unit's compare/select rate, so the
roofline of this kernel is bound by bytes alone.
"""


def work(p: int, m: int, c: int, bits: int) -> dict:
    n = 2 ** bits
    return {"flops": 0.0,
            "bytes": 4.0 * (m * c + p * c * n + 2 * c + p * m * c)}
