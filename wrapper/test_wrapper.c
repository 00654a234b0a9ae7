/*
 * test_wrapper.c — C smoke test of the embedded-interpreter ABI.
 *
 * Builds a small MLP from a config string, memorizes one random batch,
 * checks predictions, round-trips weights and a model file. Exits 0 on
 * success, prints FAIL + nonzero otherwise. Run with CXXNET_TPU_ROOT set
 * to the repo and (optionally) JAX_PLATFORMS=cpu.
 */
#define _GNU_SOURCE /* pthread_timedjoin_np */
#include "cxxnet_wrapper.h"

#include <pthread.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>

#define CHECK(cond, msg)                                   \
  do {                                                     \
    if (!(cond)) {                                         \
      fprintf(stderr, "FAIL: %s (%s)\n", msg,              \
              CXNGetLastError());                          \
      return 1;                                            \
    }                                                      \
  } while (0)

static const char *kNetCfg =
    "netconfig = start\n"
    "layer[+1:fc1] = fullc:fc1\n"
    "  nhidden = 32\n"
    "  init_sigma = 0.05\n"
    "layer[+1] = relu\n"
    "layer[+1:fc2] = fullc:fc2\n"
    "  nhidden = 10\n"
    "  init_sigma = 0.05\n"
    "layer[+0] = softmax\n"
    "netconfig = end\n"
    "input_shape = 1,1,64\n"
    "batch_size = 20\n"
    "eta = 0.1\n"
    "momentum = 0.9\n"
    "metric = error\n";

static int run_batch_leg(void) {
  const int kBatch = 20, kFeat = 64;
  cxn_real_t data[20 * 64];
  cxn_real_t label[20];
  unsigned seed = 9;
  for (int i = 0; i < kBatch * kFeat; ++i) {
    seed = seed * 1103515245u + 12345u;
    data[i] = (cxn_real_t)((seed >> 16) & 0x7fff) / 32768.0f;
  }
  for (int i = 0; i < kBatch; ++i) {
    seed = seed * 1103515245u + 12345u;
    label[i] = (cxn_real_t)((seed >> 16) % 10);
  }
  const cxn_uint dshape[4] = {20, 1, 1, 64};
  const cxn_uint lshape[2] = {20, 1};

  void *net = CXNNetCreate("cpu", kNetCfg);
  CHECK(net != NULL, "CXNNetCreate");
  CHECK(CXNNetInitModel(net) == 0, "InitModel");
  CHECK(CXNNetStartRound(net, 0) == 0, "StartRound");

  for (int step = 0; step < 200; ++step)
    CHECK(CXNNetUpdateBatch(net, data, dshape, label, lshape) == 0,
          "UpdateBatch");

  cxn_uint npred = 0;
  const cxn_real_t *pred_view = CXNNetPredictBatch(net, data, dshape, &npred);
  CHECK(pred_view != NULL && npred == 20, "PredictBatch");
  /* borrowed pointer only lives until the next call on this handle — copy */
  cxn_real_t pred[20];
  memcpy(pred, pred_view, sizeof(pred));
  int correct = 0;
  for (int i = 0; i < kBatch; ++i)
    if (pred[i] == label[i]) ++correct;
  fprintf(stderr, "memorized %d/20\n", correct);
  CHECK(correct >= 18, "should memorize the fixed batch");

  /* extract: softmax output rows sum to 1 */
  cxn_uint eshape[2] = {0, 0};
  const cxn_real_t *feat = CXNNetExtractBatch(net, data, dshape, "top[-1]",
                                              eshape);
  CHECK(feat != NULL && eshape[0] == 20 && eshape[1] == 10, "ExtractBatch");
  for (int i = 0; i < kBatch; ++i) {
    float s = 0;
    for (int j = 0; j < 10; ++j) s += feat[i * 10 + j];
    CHECK(s > 0.99f && s < 1.01f, "softmax rows must sum to 1");
  }

  /* weight round trip */
  cxn_uint wshape[2] = {0, 0};
  const cxn_real_t *w = CXNNetGetWeight(net, "fc1", "wmat", wshape);
  CHECK(w != NULL && wshape[0] == 32 && wshape[1] == 64, "GetWeight");
  cxn_real_t *wcopy = (cxn_real_t *)malloc(sizeof(cxn_real_t) * 32 * 64);
  memcpy(wcopy, w, sizeof(cxn_real_t) * 32 * 64);
  CHECK(CXNNetSetWeight(net, wcopy, wshape, "fc1", "wmat") == 0, "SetWeight");

  /* model file round trip: same predictions after load */
  CHECK(CXNNetSaveModel(net, "/tmp/cxn_wrapper_test.model") == 0,
        "SaveModel");
  void *net2 = CXNNetCreate("cpu", "");
  CHECK(net2 != NULL, "CXNNetCreate 2");
  CHECK(CXNNetLoadModel(net2, "/tmp/cxn_wrapper_test.model") == 0,
        "LoadModel");
  cxn_uint npred2 = 0;
  const cxn_real_t *pred2 = CXNNetPredictBatch(net2, data, dshape, &npred2);
  CHECK(pred2 != NULL && npred2 == 20, "PredictBatch 2");
  for (int i = 0; i < kBatch; ++i)
    CHECK(pred[i] == pred2[i], "prediction mismatch after load");
  free(wcopy);
  CXNNetFree(net2);
  CXNNetFree(net);
  fprintf(stderr, "C WRAPPER SMOKE TEST PASSED\n");
  return 0;
}

/* Second-thread leg: the ABI promises every entry point takes the GIL, so a
 * thread other than the one that initialized Python must be able to call in
 * (the embedded interpreter hands the GIL back after bootstrap). A hang here
 * means the init thread never released its base GIL hold. */
struct thread_arg {
  void *net;
  const cxn_real_t *data;
  const cxn_uint *dshape;
  int ok;
};

static void *predict_thread(void *p) {
  struct thread_arg *a = (struct thread_arg *)p;
  cxn_uint npred = 0;
  const cxn_real_t *pred = CXNNetPredictBatch(a->net, a->data, a->dshape,
                                              &npred);
  a->ok = (pred != NULL && npred == a->dshape[0]);
  return NULL;
}

static int run_thread_leg(void) {
  const int kBatch = 20, kFeat = 64;
  static cxn_real_t data[20 * 64];
  for (int i = 0; i < kBatch * kFeat; ++i)
    data[i] = (cxn_real_t)(i % 97) / 97.0f;
  const cxn_uint dshape[4] = {20, 1, 1, 64};

  void *net = CXNNetCreate("cpu", kNetCfg);
  CHECK(net != NULL, "CXNNetCreate (thread leg)");
  CHECK(CXNNetInitModel(net) == 0, "InitModel (thread leg)");

  struct thread_arg arg = {net, data, dshape, 0};
  pthread_t th;
  CHECK(pthread_create(&th, NULL, predict_thread, &arg) == 0,
        "pthread_create");
#ifdef __GLIBC__
  struct timespec deadline;
  clock_gettime(CLOCK_REALTIME, &deadline);
  deadline.tv_sec += 120;
  CHECK(pthread_timedjoin_np(th, NULL, &deadline) == 0,
        "second thread deadlocked in wrapper entry point (GIL not released "
        "after init)");
#else
  /* no timed join outside glibc; a regression here hangs instead of failing */
  CHECK(pthread_join(th, NULL) == 0, "pthread_join");
#endif
  CHECK(arg.ok, "predict from second thread");
  CXNNetFree(net);
  fprintf(stderr, "C WRAPPER THREAD LEG PASSED\n");
  return 0;
}

/* Serving leg: a tiny causal-attention LM trains a few steps, then
 * CXNNetGenerate continues two prompts KV-cached — the decode surface
 * the reference ABI never had. Ids ride the float ABI (exact < 2^24). */
static int run_generate_leg(void) {
  static const char *kLmCfg =
      "netconfig = start\n"
      "layer[0->1] = embed:emb\n"
      "  vocab_size = 12\n"
      "  nhidden = 16\n"
      "  pos_embed = 1\n"
      "  init_sigma = 0.05\n"
      "layer[1->2,3] = split\n"
      "layer[2->4] = attention:att1\n"
      "  nhead = 4\n"
      "  causal = 1\n"
      "  init_sigma = 0.05\n"
      "layer[3,4->5] = add\n"
      "layer[5->6] = conv:head\n"
      "  kernel_size = 1\n"
      "  nchannel = 12\n"
      "  random_type = kaiming\n"
      "layer[6->6] = softmax\n"
      "  seq = 1\n"
      "netconfig = end\n"
      "input_shape = 1,1,16\n"
      "batch_size = 4\n"
      "label_width = 16\n"
      "label_vec[0,16) = label\n"
      "updater = adam\n"
      "eta = 0.01\n";
  const int kB = 4, kL = 16, kVocab = 12;
  void *net = CXNNetCreate("cpu", kLmCfg);
  CHECK(net != NULL, "CXNNetCreate (lm)");
  CHECK(CXNNetInitModel(net) == 0, "InitModel (lm)");
  cxn_real_t data[4 * 16], label[4 * 16];
  const cxn_uint dshape[4] = {4, 1, 1, 16};
  const cxn_uint lshape[2] = {4, 16};
  for (int step = 0; step < 10; ++step) {
    for (int r = 0; r < kB; ++r)
      for (int t = 0; t < kL; ++t) {
        data[r * kL + t] = (cxn_real_t)((r + step + t) % kVocab);
        label[r * kL + t] = (cxn_real_t)((r + step + t + 1) % kVocab);
      }
    CHECK(CXNNetUpdateBatch(net, data, dshape, label, lshape) == 0,
          "UpdateBatch (lm)");
  }
  cxn_real_t prompts[2 * 4] = {1, 2, 3, 4, 7, 8, 9, 10};
  const cxn_uint pshape[2] = {2, 4};
  cxn_uint oshape[2] = {0, 0};
  const cxn_real_t *gen =
      CXNNetGenerate(net, prompts, pshape, 5, 0.0f, 0, 0, oshape);
  CHECK(gen != NULL && oshape[0] == 2 && oshape[1] == 5, "Generate");
  for (int i = 0; i < 2 * 5; ++i)
    CHECK(gen[i] >= 0 && gen[i] < kVocab && gen[i] == (int)gen[i],
          "generated ids must be in-vocab integers");
  /* same seed/prompts reproduce */
  cxn_real_t first[2 * 5];
  memcpy(first, gen, sizeof(first));
  const cxn_real_t *gen2 =
      CXNNetGenerate(net, prompts, pshape, 5, 0.0f, 0, 0, oshape);
  CHECK(gen2 != NULL, "Generate 2");
  for (int i = 0; i < 2 * 5; ++i)
    CHECK(first[i] == gen2[i], "greedy generate must be deterministic");
  CXNNetFree(net);
  fprintf(stderr, "C WRAPPER GENERATE LEG PASSED\n");
  return 0;
}

/* Iterator-ABI leg, enabled when argv[1] = path to an mnist data dir
 * (idx .gz files named as in example/MNIST). */
static int run_iter_leg(const char *dir);

int main(int argc, char **argv) {
  int rc = run_batch_leg();
  if (rc == 0) rc = run_thread_leg();
  if (rc == 0) rc = run_generate_leg();
  if (rc == 0 && argc > 1) rc = run_iter_leg(argv[1]);
  return rc;
}

static int run_iter_leg(const char *dir) {
  char cfg[1024];
  snprintf(cfg, sizeof(cfg),
           "iter = mnist\n"
           "  path_img = \"%s/train-images-idx3-ubyte.gz\"\n"
           "  path_label = \"%s/train-labels-idx1-ubyte.gz\"\n"
           "  batch_size = 25\n"
           "iter = end\n",
           dir, dir);
  void *it = CXNIOCreateFromConfig(cfg);
  CHECK(it != NULL, "CXNIOCreateFromConfig");
  CHECK(CXNIONext(it) == 1, "CXNIONext");
  cxn_uint ds[4], ls[2];
  const cxn_real_t *d = CXNIOGetData(it, ds);
  CHECK(d != NULL && ds[0] == 25 && ds[3] == 784, "CXNIOGetData");
  const cxn_real_t *l = CXNIOGetLabel(it, ls);
  CHECK(l != NULL && ls[0] == 25 && ls[1] == 1, "CXNIOGetLabel");

  char netcfg[512];
  snprintf(netcfg, sizeof(netcfg),
           "netconfig = start\n"
           "layer[+1:fc1] = fullc:fc1\n"
           "  nhidden = 16\n"
           "  init_sigma = 0.05\n"
           "layer[+1] = relu\n"
           "layer[+1:fc2] = fullc:fc2\n"
           "  nhidden = 10\n"
           "  init_sigma = 0.05\n"
           "layer[+0] = softmax\n"
           "netconfig = end\n"
           "input_shape = 1,1,784\n"
           "batch_size = 25\n"
           "eta = 0.2\nmomentum = 0.9\nmetric = error\n");
  void *net = CXNNetCreate("cpu", netcfg);
  CHECK(net != NULL, "net for iter leg");
  CHECK(CXNNetInitModel(net) == 0, "InitModel iter leg");
  for (int round = 0; round < 8; ++round) {
    CHECK(CXNNetStartRound(net, round) == 0, "StartRound");
    CHECK(CXNIOBeforeFirst(it) == 0, "BeforeFirst");
    while (CXNIONext(it) == 1)
      CHECK(CXNNetUpdateIter(net, it) == 0, "UpdateIter");
  }
  const char *ev = CXNNetEvaluate(net, it, "train");
  CHECK(ev != NULL, "Evaluate");
  fprintf(stderr, "eval: %s\n", ev);
  double err = atof(strstr(ev, "train-error:") + strlen("train-error:"));
  CHECK(err < 0.2, "iterator-trained net should fit");
  cxn_uint n = 0;
  CHECK(CXNIOBeforeFirst(it) == 0, "BeforeFirst 2");
  CHECK(CXNIONext(it) == 1, "Next 2");
  const cxn_real_t *p = CXNNetPredictIter(net, it, &n);
  CHECK(p != NULL && n == 25, "PredictIter");
  cxn_uint es[2];
  const cxn_real_t *f = CXNNetExtractIter(net, it, "fc1", es);
  CHECK(f != NULL && es[0] == 25 && es[1] == 16, "ExtractIter");
  CXNNetFree(net);
  CXNIOFree(it);
  fprintf(stderr, "C WRAPPER ITERATOR LEG PASSED\n");
  return 0;
}
