# Matmul throughput microbench. Reference counterpart: demo/basic_bench.R.
# NOTE on timing: dispatch is asynchronous — end the timed region with a
# data-dependent readback (docs/architecture/note_measurement.md).
require(mxnet.tpu)

n <- 512
a <- mx.nd.array(array(runif(n * n), dim = c(n, n)))
reps <- 10
t0 <- Sys.time()
for (i in seq_len(reps)) {
  a <- mx.nd.internal.invoke("dot", list(a, a), list())[[1]]
  a <- mx.nd.internal.invoke("_div_scalar", list(a),
                             list(scalar = "1000"))[[1]]
}
s <- as.array(mx.nd.internal.invoke("sum", list(a), list())[[1]])
dt <- as.numeric(Sys.time() - t0, units = "secs")
gflops <- reps * 2 * n^3 / dt / 1e9
cat("dot chain:", round(gflops, 1), "GFLOP/s (checksum", s, ")\n")
