# esslivedata-tpu service image.
#
# One image runs every role — detector/monitor/timeseries/reduction
# services, fake producers, and the dashboard — selected by the console
# script given as the container command (see docker-compose.yml). The
# default JAX wheel targets CPU; deploying on TPU hosts swaps the base
# for a TPU-enabled JAX install (the code is identical either way).

FROM python:3.12-slim AS build

RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY src ./src

RUN pip install --no-cache-dir ".[kafka,dashboard,geometry]" \
    # Compile the native ingest shim ahead of time so first ingest does
    # not pay the build; the image has g++, so a failed build fails here.
    && python -c "from esslivedata_tpu import native; assert native.available(), native.unavailable_reason()"

FROM python:3.12-slim

RUN useradd --create-home livedata
COPY --from=build /usr/local/lib/python3.12/site-packages /usr/local/lib/python3.12/site-packages
COPY --from=build /usr/local/bin /usr/local/bin

USER livedata
ENV LIVEDATA_ENV=dev \
    JAX_PLATFORMS=cpu

# Dashboard by default; compose overrides per role.
EXPOSE 5007
CMD ["esslivedata-tpu-dashboard", "--instrument", "dummy", "--transport", "kafka"]
