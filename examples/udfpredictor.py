#!/usr/bin/env python
"""UDF serving example — register a trained text classifier as a
column-level predicate over tabular data (reference
``example/udfpredictor/DataframePredictor.scala``, SURVEY §2.13: a Spark
SQL UDF that classifies a text column so queries can filter on the
predicted class).

Without Spark, the same capability is a vectorized predict function over
columnar data: ``make_predict_udf`` closes over the trained model +
vocabulary and maps a text column to predicted classes; ``query`` applies
it to a list-of-dicts table, the DataFrame stand-in
(``DLClassifierModel.transform`` drives the batched forward).

Run: ``python examples/udfpredictor.py``
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def make_predict_udf(model, word_index, table, seq_len):
    """The UDF: list-of-texts -> predicted class ids (0-based), batched
    through DLClassifierModel like the reference routes its UDF through
    the broadcast predictor."""
    from bigdl_tpu.pipeline import DLClassifierModel

    from examples.textclassification import vectorize

    embed_dim = table.shape[1]
    dl = DLClassifierModel(model, (embed_dim, 1, seq_len))

    def udf(texts):
        feats = np.stack([vectorize(t, word_index, table, seq_len)
                          for t in texts])
        return dl.transform(feats).astype(int)

    return udf


def query(rows, text_col, udf, keep_classes):
    """SELECT * FROM rows WHERE udf(text_col) IN keep_classes."""
    preds = udf([r[text_col] for r in rows])
    return [dict(r, predicted=int(p)) for r, p in zip(rows, preds)
            if int(p) in keep_classes], preds


def main():
    from examples.textclassification import main as train_main

    model, word_index, table, _ = train_main(
        ["--max-epoch", "4", "--seq-len", "150", "--synthetic-size", "250",
         "--batch-size", "16"])

    rows = [
        {"id": 1, "text": "the rocket launch reached orbit with the "
                          "satellite payload for nasa"},
        {"id": 2, "text": "the team scored a late goal to win the hockey "
                          "season opener"},
        {"id": 3, "text": "doctors recommend treatment for the patient's "
                          "health condition"},
    ]
    udf = make_predict_udf(model, word_index, table, 150)
    preds = udf([r["text"] for r in rows])
    # keep only rows the model assigns to the first predicted class —
    # the reference's "WHERE predict(text) = <class>" query shape
    kept, _ = query(rows, "text", udf, keep_classes={int(preds[0])})
    print(f"[udfpredictor] predictions: {preds.tolist()}; "
          f"{len(kept)}/{len(rows)} rows match class {int(preds[0])}")
    for r in kept:
        print(f"  id={r['id']} predicted={r['predicted']}")


if __name__ == "__main__":
    main()
