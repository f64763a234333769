"""Model operations of one printed-MLP forward over M rows: the two
matmuls, 2 M (C H + H O). Elementwise work is not counted."""


def work(m: int, c: int, h: int, o: int) -> dict:
    return {"flops": 2.0 * m * (c * h + h * o), "bytes": 0.0}
