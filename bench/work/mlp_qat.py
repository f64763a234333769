"""Model operations of one individual's quantization-aware training and
test scoring in the search (the count ``mfu.search`` is taken from).

Per step on the M training rows: the forward matmuls 2 M (C H + H O); the
backward matmuls that training needs, which are the weight gradients
2 M C H and 2 M H O and the hidden-activation gradient 2 M H O (no
gradient flows to the quantized inputs). Then one forward over the test
rows. Elementwise work is not counted.
"""


def work(m_train: int, m_test: int, c: int, h: int, o: int,
         steps: int) -> dict:
    step = 2.0 * m_train * (c * h + h * o) + 2.0 * m_train * (c * h
                                                            + 2 * h * o)
    return {"flops": steps * step + 2.0 * m_test * (c * h + h * o),
            "bytes": 0.0}
